"""The reference module as the harness's one source of architecture.

The dense reference's readings are pinned to the values the harness
gave before the architecture moved into the reference (seeded weights,
reference logits, model FLOPs), and a stub reference of a hybrid
Mamba-2/attention model goes through the harness's own functions with
nothing but the stub describing it."""
import ast
import copy
import hashlib
import importlib.util
import math
import pathlib
import re
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, costs, run, weights
from bench.references import dense
from repro.core.packing import PackedWeight
from repro.models import init_packed_params
from repro.serve import ServeEngine

from test_benchmark_control import MIX, PEAK, TINY

ROOT = pathlib.Path(__file__).resolve().parents[2]
SEED = 2**31 + 5

UNTIED = copy.deepcopy(TINY)
UNTIED["program"]["overrides"]["tie_embeddings"] = False
UNTIED["model"]["tied_embedding"] = False


def _digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(leaf)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _template(config):
    cfg = run.program_config(config)
    return cfg, jax.eval_shape(
        lambda: init_packed_params(jax.random.PRNGKey(0), cfg, config["n_bits"]))


# -- the dense reference reads as the harness did before ---------------------

@pytest.mark.parametrize("config,want", [
    (TINY, "8d08e03185527d4fb5b89fbd015502a9ae7e68e6bd4d3a4207b9f99f73efb4e5"),
    (UNTIED, "9d817d7c8e5be7dd15367d023ec55ecc9dbc1842b4b9a14312f9d605e2bccc58"),
], ids=["tied", "untied"])
def test_seeded_weights_are_bit_identical(config, want):
    """Every leaf ``program_params`` draws, byte for byte, as the harness
    drew it when the roles and weight scales were its own."""
    cfg, template = _template(config)
    params = weights.program_params(SEED, template, check.reference(config), config["model"],
                                    config["n_bits"], cfg.pattern_len, PackedWeight)
    assert _digest(params) == want


@pytest.mark.parametrize("config,precision,last_row,amax", [
    (TINY, "f32", [-1.08016037940979, -1.1916062831878662, -0.22002963721752167,
                   1.183394193649292], 3.7598249912261963),
    (TINY, "fp8", [-1.1423147916793823, -1.1020933389663696, 0.035779573023319244,
                   1.3003478050231934], 3.6970624923706055),
    (UNTIED, "f32", [-0.0028990954160690308, 0.37874579429626465, 1.1218560934066772,
                     1.8741371631622314], 4.245038986206055),
], ids=["tied-f32", "tied-fp8", "untied-f32"])
def test_reference_logits_are_unchanged(config, precision, last_row, amax):
    """The reference regenerates its layers from the same leaves (float32
    on the CPU; the tolerance allows only for a matmul's summation order)."""
    toks = np.arange(24, dtype=np.int32) * 37 % config["model"]["vocab"]
    got = np.asarray(dense.logits(SEED, toks, config["model"], config["n_bits"], precision))
    np.testing.assert_allclose(got[-1, :4], last_row, rtol=1e-5, atol=1e-5)
    assert float(np.abs(got).max()) == pytest.approx(amax, rel=1e-5)


@pytest.mark.parametrize("positions,want", [
    ([0], 721920.0), ([0, 5, 17], 2188288.0), (list(range(40)), 29675520.0)])
def test_dense_model_flops_at_the_tiny_size(positions, want):
    dims = dense.dims_of(TINY["model"])
    assert dense.model_flops(TINY["model"], dims, positions) == want


def test_dense_layers_all_attend_and_hold_every_role():
    dims = dense.dims_of(TINY["model"])
    L = TINY["model"]["n_layers"]
    assert dims["kinds"] == ("attn",) * L and dims["attn_layers"] == L
    assert set(dims["roles"]["attn"]) == set(dims["matrices"]) | {"norm1", "norm2"}
    assert dense.program_differs(TINY["model"], run.program_config(TINY)) == {}
    other = dict(TINY["model"], d_ff=512)
    assert dense.program_differs(other, run.program_config(TINY)) == {"d_ff": (512, 256)}


# -- a second architecture with no harness edit -------------------------------

STUB = "stub_hybrid"
ATTN = ("wq", "wk", "wv", "wo")
MLP = ("w_gate", "w_up", "w_down")
NORMS = ("norm1", "norm2")
SSM = ("in_proj", "conv_w", "conv_b", "a_log", "dt_bias", "d_skip", "norm", "out_proj")
HYBRID = {
    "name": "hybrid", "reference": STUB, "n_bits": 4,
    # as a JSON file gives it: the layer pattern is a list
    "program": {"arch": "granite-3-2b", "overrides": {
        "d_model": 128, "n_heads": 4, "n_kv_heads": 2, "d_ff": 256, "n_layers": 4,
        "vocab_size": 512, "norm_eps": 1e-5, "layer_pattern": ["ssm", "attn"],
        "ssm_state": 16, "ssm_head_dim": 32, "ssm_chunk": 16}},
    "model": {"d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
              "n_layers": 4, "vocab": 512, "vocab_rows": 512, "layer_pattern": ["ssm", "attn"],
              "ssm_state": 16},
}


def _stub_reference(float_leaves=None):
    """A reference module of a hybrid model: Mamba-2 layers (float
    projections, conv, decay, skip and a gated norm) beside attention
    layers, each with the gated MLP."""
    def dims_of(model):
        d, hd, L = model["d_model"], model["head_dim"], model["n_layers"]
        H, KV, F = model["n_heads"], model["n_kv_heads"], model["d_ff"]
        mats = {"wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd), "wo": (H * hd, d),
                "w_gate": (d, F), "w_up": (d, F), "w_down": (F, d)}
        pattern = model["layer_pattern"]
        kinds = tuple(pattern[l % len(pattern)] for l in range(L))
        return dict(mats, matrices=tuple(mats), n_layers=L, d_model=d, kinds=kinds,
                    roles={"attn": ATTN + MLP + NORMS, "ssm": SSM + MLP + NORMS},
                    attn_layers=kinds.count("attn"))

    def normal(std):
        return lambda key, shape: std * jax.random.normal(key, shape, jnp.float32)

    def model_flops(model, dims, positions):
        per_layer = {kind: sum(math.prod(dims[r]) for r in roles if r in dims)
                     for kind, roles in dims["roles"].items()}
        w = sum(per_layer[k] for k in dims["kinds"]) + model["d_model"] * model["vocab"]
        attn = 4.0 * dims["attn_layers"] * model["n_heads"] * model["head_dim"]
        return sum(2.0 * w + attn * (p + 1) for p in positions)

    def program_differs(model, cfg):
        want = {"layer_pattern": list(cfg.layer_pattern), "n_layers": cfg.n_layers,
                "ssm_state": cfg.ssm_state, "d_model": cfg.d_model}
        return {k: (model[k], v) for k, v in want.items() if model[k] != v}

    mod = types.ModuleType(f"bench.references.{STUB}")
    mod.dims_of, mod.model_flops, mod.program_differs = dims_of, model_flops, program_differs
    mod.weight_std = lambda role, k, n_layers: 1.0 / math.sqrt(k)
    mod.FLOAT_LEAVES = float_leaves if float_leaves is not None else {
        "in_proj": normal(0.05), "out_proj": normal(0.05), "conv_w": normal(0.1),
        "conv_b": normal(0.01), "dt_bias": normal(0.1),
        "a_log": lambda key, shape: jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)),
        "d_skip": lambda key, shape: jnp.ones(shape, jnp.float32)}
    return mod


@pytest.fixture
def stub(monkeypatch):
    mod = _stub_reference()
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    return mod


def test_a_hybrid_model_is_built_from_its_reference_alone(stub):
    """``run.build`` finds the stub by the configuration's ``reference``
    key; the stub's roles and float leaves fill every leaf of the
    program's hybrid tree, and the engine serves its warm-up."""
    assert check.reference(HYBRID) is stub
    cfg, template = _template(HYBRID)
    engine, params, _ = run.build(HYBRID, MIX, 5, jax.devices("cpu")[0])
    assert isinstance(engine, ServeEngine)
    is_pw = lambda x: isinstance(x, PackedWeight)
    shapes = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t, is_leaf=is_pw)
    assert jax.tree.structure(params, is_leaf=is_pw) == jax.tree.structure(template,
                                                                           is_leaf=is_pw)
    got = [(l.planes.shape, l.sign.shape) if is_pw(l) else (l.shape, l.dtype)
           for l in jax.tree.leaves(params, is_leaf=is_pw)]
    want = [(l.planes.shape, l.sign.shape) if is_pw(l) else (l.shape, l.dtype)
            for l in jax.tree.leaves(template, is_leaf=is_pw)]
    assert got == want, shapes(params)
    ssm, attn = params["blocks"]["p0"], params["blocks"]["p1"]
    assert "mixer" in ssm and "a_log" in ssm["mixer"] and is_pw(attn["mixer"]["wq"])
    heads = ssm["mixer"]["a_log"].shape[-1]
    np.testing.assert_allclose(ssm["mixer"]["a_log"][0], np.log(np.arange(1, heads + 1)),
                               rtol=1e-6)
    # the gated norm is a gain like the block norms: drawn, held as gain - 1
    assert 0 < float(jnp.abs(ssm["mixer"]["norm"]["scale"]).max()) < 1
    # each layer's leaves come from its own key
    assert not np.array_equal(ssm["mixer"]["conv_w"][0], ssm["mixer"]["conv_w"][1])


def test_a_leaf_the_reference_does_not_name_raises(monkeypatch):
    leaves = dict(_stub_reference().FLOAT_LEAVES)
    del leaves["conv_b"]
    mod = _stub_reference(leaves)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    cfg, template = _template(HYBRID)
    with pytest.raises(ValueError, match="blocks/p0/mixer/conv_b"):
        weights.program_params(SEED, template, mod, HYBRID["model"], HYBRID["n_bits"],
                               cfg.pattern_len, PackedWeight)


def _reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_readers_count_the_references_attention_layers(stub):
    """Half the hybrid's layers attend: paged attention's least time and
    the model's attention FLOPs count two layers of four."""
    model = HYBRID["model"]
    dims = stub.dims_of(model)
    assert dims["attn_layers"] == 2
    blocks, block_size = 50, 32
    ctx = {"model": model, "dims": dims, "reference": stub, "peak": PEAK,
           "block_size": block_size, "at_open": {"attn_blocks": 10},
           "at_close": {"attn_blocks": 10 + blocks},
           "trace": {"kernels": {("decode", "paged_attention"): {"n": 4, "device_s": 1e-3}}},
           "events": {0: [("prefill_chunk", 1.0, {"size": 3}), ("decode_step", 1.5, {})]},
           "prompt_len": {0: 3}, "t_open": 0.0, "t_close": 2.0}
    flops, nbytes = costs.paged_attention(blocks * block_size, 4, 2, 32)
    want = 100.0 * costs.least_time(2 * flops, 2 * nbytes, PEAK)[0] / 1e-3
    assert _reader("paged_attention_roofline")(ctx) == pytest.approx(want)
    # positions 0, 1, 2 prefilled and 3 decoded: the MLP in four layers,
    # q, k, v, o and attention over p + 1 positions in two, and the head
    matmul = 4 * 3 * 128 * 256 + 2 * (2 * 128 * 128 + 2 * 128 * 64) + 128 * 512
    flops = 4 * 2.0 * matmul + 4.0 * 2 * 4 * 32 * (1 + 2 + 3 + 4)
    assert _reader("step_mfu")(ctx) == pytest.approx(100.0 * flops / (2.0 * PEAK["bf16_flops"]))


def test_no_harness_module_imports_a_reference_by_name():
    """Only ``check.reference`` reaches ``bench/references/``, by the
    configuration's ``reference`` key."""
    found = []
    for path in sorted((ROOT / "bench").rglob("*.py")):
        if "references" in path.relative_to(ROOT / "bench").parts:
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                base = "." * node.level + (node.module or "")
                names = [base] + [f"{base}.{a.name}" for a in node.names]
            elif isinstance(node, ast.Constant) and isinstance(node.value, str):
                names = re.findall(r"bench\.references\.\w+", node.value)
            found += [f"{path.relative_to(ROOT)}: {n}" for n in names
                      if n.lstrip(".").startswith("references")
                      or n.startswith("bench.references")]
    assert found == []
