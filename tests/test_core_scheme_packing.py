"""QuantScheme accounting + sign-magnitude packing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantScheme, decompose, pack_from_float, scheme_from_reps, unpack_to_float
from repro.core.packing import pack_quantized, unpack_bits_axis0


def test_scheme_compression_math():
    s = QuantScheme(bits={"a": np.array(4), "b": np.array(2)}, group_numel={"a": 100, "b": 300})
    assert s.quantized_params == 400
    assert s.total_bits == 4 * 100 + 2 * 300
    np.testing.assert_allclose(s.bits_per_param, 1000 / 400)
    np.testing.assert_allclose(s.compression, 32 * 400 / 1000)


def test_scheme_grouped_bits():
    s = QuantScheme(bits={"a": np.array([4, 0])}, group_numel={"a": 50})
    assert s.total_bits == 200
    assert s.quantized_params == 100


def test_scheme_json_roundtrip():
    s = QuantScheme(bits={"x": np.array([3, 5])}, group_numel={"x": 10}, float_params=7)
    s2 = QuantScheme.from_json(s.to_json())
    np.testing.assert_array_equal(s2.bits["x"], s.bits["x"])
    assert s2.group_numel == s.group_numel and s2.float_params == 7


def test_scheme_from_reps():
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 8))
    reps = {"w": decompose(w, 5, group_axes=(0,))}
    s = scheme_from_reps(reps)
    np.testing.assert_array_equal(s.bits["w"].ravel(), [5, 5, 5, 5])
    assert s.group_numel["w"] == 64


@pytest.mark.parametrize("n_bits", [1, 3, 5, 8])
@pytest.mark.parametrize("shape", [(8, 16), (64, 32), (100, 8)])
def test_pack_roundtrip_error(n_bits, shape):
    w = jax.random.normal(jax.random.PRNGKey(1), shape) * 2.0
    pw = pack_from_float(w, n_bits)
    err = float(jnp.max(jnp.abs(unpack_to_float(pw) - w)))
    bound = 0.5 * float(jnp.max(jnp.abs(w))) / (2**n_bits - 1) * (1 + 1e-4)
    assert err <= bound


def test_pack_exact_integer_codes():
    q = jnp.array([[-7, 3], [0, 5], [7, -1], [2, 2], [1, 1], [0, 0], [-3, -3], [4, 4]],
                  jnp.int32)
    pw = pack_quantized(q, jnp.float32(7.0), 3)
    got = np.asarray(unpack_to_float(pw))
    np.testing.assert_allclose(got, np.asarray(q, np.float32), rtol=1e-6)


def test_unpack_bits_inverse():
    bits = (jax.random.uniform(jax.random.PRNGKey(2), (64, 16)) > 0.5).astype(jnp.uint8)
    from repro.core.packing import _pack_bits_axis0_groups_of_8

    packed = _pack_bits_axis0_groups_of_8(bits)
    np.testing.assert_array_equal(np.asarray(unpack_bits_axis0(packed, 64)), np.asarray(bits))


def test_hbm_bytes_scales_with_precision():
    w = jax.random.normal(jax.random.PRNGKey(3), (256, 256))
    b3 = pack_from_float(w, 3).hbm_bytes()
    b8 = pack_from_float(w, 8).hbm_bytes()
    bf16 = 256 * 256 * 2
    assert b3 < b8 < bf16
    np.testing.assert_allclose(b3 / bf16, (3 + 1) / 16, rtol=0.05)


@pytest.mark.parametrize("arch", ["granite-3-2b", "recurrentgemma-9b", "qwen2-moe-a2.7b"])
def test_init_packed_params_equals_pack_of_init(arch):
    """The streaming packed init (one jitted program per superblock, so a
    published-width model packs on one chip) builds the tree the float
    init followed by pack_model_params builds: same structure, shapes,
    dtypes and float leaves, and packed weights within one quantisation
    step (a fused jitted draw may differ from the op-by-op one in the
    last ulp, which can move a code that sits on a rounding boundary)."""
    from repro.configs import reduced_config
    from repro.core.packing import PackedWeight, pack_model_params
    from repro.models import init_packed_params, init_params

    cfg = reduced_config(arch)
    key = jax.random.PRNGKey(7)
    want = pack_model_params(init_params(key, cfg), 6)
    got = init_packed_params(key, cfg, 6)
    is_pw = lambda x: isinstance(x, PackedWeight)
    assert jax.tree.structure(got, is_leaf=is_pw) == jax.tree.structure(want, is_leaf=is_pw)
    n_packed = 0
    for g, w in zip(jax.tree.leaves(got, is_leaf=is_pw), jax.tree.leaves(want, is_leaf=is_pw)):
        if is_pw(w):
            n_packed += 1
            assert (g.planes.shape, g.sign.shape, g.scale.shape) == (
                w.planes.shape, w.sign.shape, w.scale.shape)
            np.testing.assert_allclose(np.asarray(g.scale), np.asarray(w.scale), rtol=1e-6)
            step = np.asarray(w.scale) / (2**6 - 1)
            diff = np.abs(np.asarray(unpack_to_float(g)) - np.asarray(unpack_to_float(w)))
            assert np.all(diff <= step * 1.001)
        else:
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-7)
    assert n_packed > 0
