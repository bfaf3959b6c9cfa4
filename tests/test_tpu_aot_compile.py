"""Ahead-of-time compiles of the serving path's Pallas kernels for a
described (not attached) TPU v5e, at granite-3-2b widths.

Interpret mode runs the kernel bodies on the CPU but accepts block
shapes, casts and reshapes that the TPU compiler (Mosaic) refuses; these
compiles catch such refusals with no chip.  Widths: d_model 2048, d_ff
8192, 32/8 heads of 64, 6-bit packed weights, 8 decode slots and 32-row
KV blocks.  Decode dispatches M = n_slots rows; a prefill chunk of C
tokens dispatches M = n_slots * C.

The benchmark reads a device trace by names: the programs' jitted
function names (``jit__decode_fn``, ``jit_chunk_into_pool``) and the
kernels' ``pallas_call`` names, which name their HLO instructions
(``%bitserial_matmul_pallas.N``, ``%paged_attention_pallas.N``).  These
tests pin both, so a rename fails here instead of silencing the
benchmark's step times and rooflines.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.packing import PackedWeight
from repro.kernels import ops

N_SLOTS, CHUNK, N_BITS = 8, 128, 6
D_MODEL, D_FF, N_KV, G, HEAD_DIM = 2048, 8192, 8, 4, 64
BLOCK, MAX_LEN = 32, 1024


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or the library is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # An AOT compile for an absent chip cannot be read back from the
    # persistent cache, so keep it out of the cache entirely.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, kernel, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{kernel}" in text  # the instruction name the trace is read by
    return compiled


def _packed(dev, K, N):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=dev)
    return PackedWeight(
        planes=s((N_BITS, K // 8, N), jnp.uint8), sign=s((K // 8, N), jnp.uint8),
        scale=s((1, 1), jnp.float32), n_bits=N_BITS, k=K)


@pytest.mark.parametrize("dynamic", [False, True], ids=["static", "dynamic"])
@pytest.mark.parametrize("M", [N_SLOTS, N_SLOTS * CHUNK, 3], ids=["decode", "prefill", "ragged"])
@pytest.mark.parametrize("K,N", [(D_MODEL, D_FF), (D_FF, D_MODEL)], ids=["up", "down"])
def test_bitserial_matmul_compiles(one_chip, K, N, M, dynamic):
    x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)
    pw = _packed(one_chip, K, N)
    if dynamic:
        a = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        fn = lambda x, pw, a: ops.bitserial_matmul(
            x, pw, active_planes=a, use_pallas=True, interpret=False)
        out = _compile(fn, "bitserial_matmul_pallas", x, pw, a)
    else:
        fn = lambda x, pw: ops.bitserial_matmul(x, pw, use_pallas=True, interpret=False)
        out = _compile(fn, "bitserial_matmul_pallas", x, pw)
    assert out.out_info.shape == (M, N)


@pytest.mark.parametrize("window", [None, 256], ids=["full", "window"])
def test_paged_attention_compiles(one_chip, window):
    nb_lane = MAX_LEN // BLOCK
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = s((N_SLOTS, N_KV, G, HEAD_DIM), jnp.bfloat16)
    pool = s((N_SLOTS * nb_lane, N_KV, BLOCK, HEAD_DIM), jnp.bfloat16)
    table = s((N_SLOTS, nb_lane), jnp.int32)
    pos = s((N_SLOTS,), jnp.int32)
    fn = lambda q, k, v, t, p: ops.paged_attention(
        q, k, v, t, p, window=window, use_pallas=True, interpret=False)
    out = _compile(fn, "paged_attention_pallas", q, pool, pool, table, pos)
    assert out.out_info.shape == q.shape


def test_serving_programs_keep_their_jitted_names():
    """On the CPU: the paged engine's decode and chunk programs lower to
    the modules the trace's ``XLA Modules`` line names."""
    from repro.configs import reduced_config
    from repro.models import init_params
    from repro.serve import ServeEngine

    cfg = reduced_config("granite-3-2b")
    eng = ServeEngine(init_params(jax.random.PRNGKey(0), cfg), cfg, max_len=32,
                      continuous=True, n_slots=2, chunked_prefill=True, paged=True,
                      block_size=4, paged_kernel=True)
    sched, pool = eng.scheduler, eng.scheduler.pool
    decode = sched._decode.lower(eng.params, pool.cache, pool.tok, pool.pos, pool.act,
                                 pool.block_table)
    assert "module @jit__decode_fn " in decode.as_text()
    ctrl = jnp.zeros((pool.n_slots,), jnp.int32)
    chunk = sched._chunk_fn(1).lower(eng.params, pool.cache, ctrl[:, None], ctrl, ctrl,
                                     pool.block_table)
    assert "module @jit_chunk_into_pool " in chunk.as_text()
