"""Pallas TPU kernel: block-table-walking paged decode attention.

Decode reads are where a paged serve engine lives or dies: the jnp
reference path in ``models.attention`` gathers each lane's *entire*
logical view out of the global pool every step, so HBM traffic is
O(blocks_per_lane x block_size) no matter how few tokens are live.
This kernel instead walks each lane's block table block-by-block with
flash-style online softmax (running max / denominator / accumulator in
VMEM scratch, the same tiling discipline as ``flash_attention.py``) and
skips dead blocks, so per-step bytes scale with live tokens.

Layout contract (mirrors ``SlotPool`` / ``init_cache``):

* ``q``          (B, KV, G, d)  single decode query per lane, kv-major
  GQA head layout (head h = kv * G + g, matching ``_gqa_scores``).
* ``k_pool/v_pool`` (n_blocks, KV, block_size, d)  the global pools,
  head-major so one grid step's K/V block is a whole (block_size, d)
  tile of one KV head (a legal TPU block: both dims are full).
* ``block_table`` (B, blocks_per_lane) int32  pool block id of each
  lane-logical block; stale/unallocated entries may hold anything.
* ``pos``        (B,) int32  last written row per lane; ``pos < 0``
  marks an inactive lane and produces exact zeros.

Grid is ``(B, KV, blocks_per_lane)`` with the table walk innermost.
``block_table`` and ``pos`` ride in as scalar-prefetch operands
(`PrefetchScalarGridSpec`), so the K/V BlockSpec index_maps can chase
the table: step ``j`` of lane ``b`` maps the K/V block to pool block
``table[b, clip(j, lo, hi)]`` where ``[lo, hi]`` is the lane's live
range (``hi = pos // bs``, ``lo`` from the sliding window).  Clamping
freezes the index outside the live range, and Pallas only issues a DMA
when a BlockSpec index *changes* between steps — so skipped blocks cost
no HBM reads, and ``@pl.when`` skips their compute as well.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, out_ref,
            acc_ref, m_ref, l_ref, *, block_size: int, nb_lane: int,
            window: int | None, sm_scale: float):
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos_b = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    k_start = j * block_size
    # Live block: holds at least one row this lane's single query sees.
    needed = (pos_b >= 0) & (k_start <= pos_b)
    if window is not None:
        needed &= (pos_b - (k_start + block_size - 1)) < window

    def run():
        q = q_ref[...]                       # (G, d)
        k = k_ref[...].astype(q.dtype)       # (bs, d)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                         # (G, bs)
        kpos = k_start + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], block_size), 1)
        mask = kpos <= pos_b
        if window is not None:
            mask &= (pos_b - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    pl.when(needed)(run)

    @pl.when(j == nb_lane - 1)
    def _finish():
        # l == 0 (pos < 0: no block ever ran) -> exact zeros.
        out_ref[...] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "sm_scale", "interpret"))
def paged_attention_pallas(q, k_pool, v_pool, block_table, pos, *,
                           window=None, sm_scale=None, interpret=False):
    """Paged decode attention; see module docstring for the layout."""
    B, KV, G, d = q.shape
    bs = k_pool.shape[2]
    nb_lane = block_table.shape[1]
    if sm_scale is None:
        sm_scale = d**-0.5
    block_table = block_table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)

    def kv_map(b, h, j, tbl, pos_):
        p_b = pos_[b]
        hi = jnp.clip(p_b // bs, 0, nb_lane - 1)
        lo = 0
        if window is not None:
            lo = jnp.clip((p_b - window + 1) // bs, 0, nb_lane - 1)
        # Frozen outside [lo, hi]: the index repeats, so Pallas issues
        # no DMA for the blocks @pl.when skips.
        jm = jnp.clip(j, lo, hi)
        return (tbl[b, jm], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, nb_lane),
        in_specs=[
            pl.BlockSpec((None, None, G, d), lambda b, h, j, tbl, pos_: (b, h, 0, 0)),
            pl.BlockSpec((None, None, bs, d), kv_map),
            pl.BlockSpec((None, None, bs, d), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, G, d), lambda b, h, j, tbl, pos_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, d), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
    )
    kern = functools.partial(
        _kernel, block_size=bs, nb_lane=nb_lane, window=window,
        sm_scale=float(sm_scale))
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, d), q.dtype),
        interpret=interpret,
    )(block_table, pos, q, k_pool, v_pool)
