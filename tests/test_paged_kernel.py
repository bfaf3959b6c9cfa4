"""Conformance for the Pallas block-table-walking decode kernel.

``kernels.paged_attention.paged_attention_pallas`` must match the naive
f32 gather reference (``kernels.ref.paged_attention_ref``) across block
sizes, ragged live lengths, GQA ratios, sliding windows and inactive
lanes — run in interpret mode so CPU CI exercises the real kernel body
(grid walk, ``@pl.when`` skipping, online-softmax scratch), not just the
dispatch wrapper.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import paged_attention_ref


def _case(seed, *, B=3, n_kv=2, G=2, d=16, bs=4, nb_lane=6, dtype=jnp.float32):
    """Seeded inputs with lane-disjoint SHUFFLED tables: logical block
    order != pool order, the indirection the kernel must honour."""
    rng = np.random.default_rng(seed)
    n_blocks = B * nb_lane + 2  # a couple of never-referenced pool blocks
    q = jnp.asarray(rng.normal(size=(B, n_kv, G, d)), dtype)
    k_pool = jnp.asarray(rng.normal(size=(n_blocks, n_kv, bs, d)), dtype)
    v_pool = jnp.asarray(rng.normal(size=(n_blocks, n_kv, bs, d)), dtype)
    table = jnp.asarray(
        rng.permutation(n_blocks)[: B * nb_lane].reshape(B, nb_lane), jnp.int32)
    return q, k_pool, v_pool, table


def _check(q, k_pool, v_pool, table, pos, window=None, tol=2e-5):
    pos = jnp.asarray(pos, jnp.int32)
    got = ops.paged_attention(q, k_pool, v_pool, table, pos, window=window,
                              use_pallas=True, interpret=True)
    want = paged_attention_ref(q, k_pool, v_pool, table, pos, window=window)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("bs,nb_lane", [(2, 12), (4, 6), (8, 3)])
def test_block_sizes(bs, nb_lane):
    q, k, v, tbl = _case(0, bs=bs, nb_lane=nb_lane)
    _check(q, k, v, tbl, [bs * nb_lane - 1, bs + 1, 0])


@pytest.mark.parametrize("seed", range(4))
def test_ragged_live_lengths(seed):
    """Per-lane positions anywhere in [0, capacity): per-lane work (and
    masking within the last partial block) must stay independent."""
    q, k, v, tbl = _case(seed)
    rng = np.random.default_rng(100 + seed)
    pos = rng.integers(0, 4 * 6, size=3)
    _check(q, k, v, tbl, pos)


@pytest.mark.parametrize("n_kv,G", [(1, 4), (2, 2), (4, 1), (2, 4)])
def test_gqa_ratios(n_kv, G):
    q, k, v, tbl = _case(1, n_kv=n_kv, G=G)
    _check(q, k, v, tbl, [17, 5, 0])


@pytest.mark.parametrize("window", [1, 3, 5, 64])
def test_sliding_window(window):
    """Windowed lanes attend to exactly the last `window` rows — blocks
    wholly behind the window are skipped AND masked identically."""
    q, k, v, tbl = _case(2)
    _check(q, k, v, tbl, [23, 7, 2], window=window)


def test_inactive_lanes_exact_zero():
    """pos < 0 marks a lane inactive (free / mid-prefill): the kernel
    must emit exact zeros there (no NaN from an empty softmax) while
    active neighbours are untouched."""
    q, k, v, tbl = _case(3)
    pos = jnp.asarray([-1, 9, -1], jnp.int32)
    out = ops.paged_attention(q, k, v, tbl, pos, use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out[0]), 0.0)
    np.testing.assert_array_equal(np.asarray(out[2]), 0.0)
    _check(q, k, v, tbl, pos)
    _check(q, k, v, tbl, [-1, -1, -1])


def test_stale_table_entries_never_read():
    """Entries past a lane's live length are dead (stale ids from an
    evicted tenant): scrambling them must not change the output — the
    walk stops at the last live block instead of trusting pool capacity."""
    q, k, v, tbl = _case(4)
    pos = [9, 3, 0]  # live blocks per lane: 3, 1, 1 (of 6)
    base = ops.paged_attention(q, k, v, tbl, jnp.asarray(pos, jnp.int32),
                               use_pallas=True, interpret=True)
    live = [3, 1, 1]
    scrambled = np.asarray(tbl).copy()
    for b in range(3):
        scrambled[b, live[b]:] = (scrambled[b, live[b]:] + 5) % k.shape[0]
    got = ops.paged_attention(q, k, v, jnp.asarray(scrambled), jnp.asarray(pos, jnp.int32),
                              use_pallas=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(got))


def test_bf16_cache():
    """bf16 K/V pool with f32 query: the kernel upcasts per-block and
    accumulates in f32 scratch, so it tracks the f32 reference to bf16
    resolution."""
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(2, 2, 2, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(14, 2, 4, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(14, 2, 4, 16)), jnp.bfloat16)
    tbl = jnp.asarray(rng.permutation(14)[:12].reshape(2, 6), jnp.int32)
    pos = jnp.asarray([20, 6], jnp.int32)
    got = ops.paged_attention(q, k, v, tbl, pos, use_pallas=True, interpret=True)
    want = paged_attention_ref(q, k, v, tbl, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_ref_matches_dense_softmax():
    """Anchor the reference itself: with an identity block table the
    paged ref reduces to plain causal single-query attention."""
    rng = np.random.default_rng(6)
    B, KV, G, d, bs, nb = 2, 2, 2, 8, 4, 3
    q = jnp.asarray(rng.normal(size=(B, KV, G, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B * nb, KV, bs, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B * nb, KV, bs, d)), jnp.float32)
    tbl = jnp.arange(B * nb, dtype=jnp.int32).reshape(B, nb)
    pos = jnp.asarray([bs * nb - 1, 5], jnp.int32)
    out = paged_attention_ref(q, k, v, tbl, pos)
    keys = k.reshape(B, nb, KV, bs, d).transpose(0, 1, 3, 2, 4).reshape(B, nb * bs, KV, d)
    vals = v.reshape(B, nb, KV, bs, d).transpose(0, 1, 3, 2, 4).reshape(B, nb * bs, KV, d)
    for b in range(B):
        for kv in range(KV):
            for g in range(G):
                s = keys[b, : pos[b] + 1, kv] @ q[b, kv, g] * d ** -0.5
                w = np.exp(s - s.max())
                w /= w.sum()
                want = w @ vals[b, : pos[b] + 1, kv]
                np.testing.assert_allclose(np.asarray(out[b, kv, g]), want,
                                           atol=1e-5, rtol=1e-5)
