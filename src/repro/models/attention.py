"""Attention: GQA/MQA/MHA, full + sliding-window causal, cross-attn.

Prefill/train uses a query-chunked (flash-style) path by default so the
score tensor never materialises at (S, S); decode is a single-query read
over a preallocated KV cache.  All softmax math in f32.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import apply_rope, dense_apply, dense_init, paged_mesh

Params = Dict[str, jax.Array]

NEG_INF = -1e30


def attn_init(key, d_model: int, n_heads: int, n_kv: int, head_dim: int) -> Params:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, d_model, n_heads * head_dim),
        "wk": dense_init(k2, d_model, n_kv * head_dim),
        "wv": dense_init(k3, d_model, n_kv * head_dim),
        "wo": dense_init(k4, n_heads * head_dim, d_model),
    }


def _qkv(p: Params, x: jax.Array, n_heads: int, n_kv: int, head_dim: int):
    B, S, _ = x.shape
    q = dense_apply(x, p["wq"]).reshape(B, S, n_heads, head_dim)
    k = dense_apply(x, p["wk"]).reshape(B, S, n_kv, head_dim)
    v = dense_apply(x, p["wv"]).reshape(B, S, n_kv, head_dim)
    return q, k, v


def _gqa_scores(q: jax.Array, k: jax.Array, dtype=jnp.float32) -> jax.Array:
    """q: (B, Sq, K, G, d); k: (B, Sk, K, d) -> (B, K, G, Sq, Sk)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=dtype)


def _gqa_combine(w: jax.Array, v: jax.Array, dtype) -> jax.Array:
    """w: (B, K, G, Sq, Sk); v: (B, Sk, K, d) -> (B, Sq, K*G*d)."""
    o = jnp.einsum("bkgqs,bskd->bqkgd", w.astype(dtype), v)
    B, Sq = o.shape[0], o.shape[1]
    return o.reshape(B, Sq, -1)


def _mask(q_pos: jax.Array, k_pos: jax.Array, window: Optional[int]) -> jax.Array:
    """(Sq, Sk) boolean: causal, optionally sliding-window."""
    m = q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= (q_pos[:, None] - k_pos[None, :]) < window
    return m


def attention(
    p: Params,
    x: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[int] = None,
    q_chunk: int = 1024,
    positions: Optional[jax.Array] = None,
    unroll: bool = False,
    scores_dtype=jnp.float32,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Causal self-attention for train/prefill.  Returns (out, (k, v)) so
    prefill can seed the decode cache."""
    B, S, _ = x.shape
    G = n_heads // n_kv
    q, k, v = _qkv(p, x, n_heads, n_kv, head_dim)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    q = q.reshape(B, S, n_kv, G, head_dim) * (head_dim**-0.5)

    kpos = jnp.arange(S)

    def block(qc: jax.Array, q0: jax.Array) -> jax.Array:
        qpos = q0 + jnp.arange(qc.shape[1])
        s = _gqa_scores(qc, k, scores_dtype)
        m = _mask(qpos, kpos, window)
        s = jnp.where(m[None, None, None], s, jnp.asarray(NEG_INF, scores_dtype))
        # max-subtraction keeps bf16 scores numerically safe
        s = s - jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
        w = jax.nn.softmax(s.astype(scores_dtype), axis=-1)
        return _gqa_combine(w, v, x.dtype)

    if S <= q_chunk:
        out = block(q, jnp.int32(0))
    else:
        assert S % q_chunk == 0, (S, q_chunk)
        nq = S // q_chunk
        qs = q.reshape(B, nq, q_chunk, n_kv, G, head_dim).transpose(1, 0, 2, 3, 4, 5)

        if unroll:  # dry-run accounting path (cost_analysis vs while loops)
            outs = jnp.stack([block(qs[i], jnp.int32(i * q_chunk)) for i in range(nq)])
        else:
            def step(_, inp):
                qc, i = inp
                return None, block(qc, i * q_chunk)

            _, outs = jax.lax.scan(step, None, (qs, jnp.arange(nq)))
        out = outs.transpose(1, 0, 2, 3).reshape(B, S, -1)
    return dense_apply(out, p["wo"]), (k, v)


def _pool_gather(cache_k, cache_v, block_table, n_kv: int, head_dim: int):
    """Lane-logical (B, nb_lane*bs, K, d) views of both (n_blocks, K, bs,
    d) pools.

    The flattened table index is built once and shared by the K and V
    gathers (they are two reads, one index computation)."""
    B, nb_lane = block_table.shape
    idx = block_table.reshape(-1)

    def view(pool):
        blocks = jnp.take(pool, idx, axis=0).reshape(B, nb_lane, n_kv, -1, head_dim)
        return blocks.transpose(0, 1, 3, 2, 4).reshape(B, -1, n_kv, head_dim)

    return view(cache_k), view(cache_v)


def _paged_update_attend(
    q_heads, k_row, v_row, cache_k, cache_v, block_table, pos, active, *,
    n_kv: int, head_dim: int, window: Optional[int], use_kernel: bool, x_dtype,
):
    """Scatter one decode row through the block table, then attend.

    ``q_heads``/``k_row``/``v_row``: (B, H, d) / (B, K, d) post-RoPE,
    unscaled; returns ``(out (B, K, G, d), new_k, new_v)``.  All block
    ids are table-relative, so the same function runs globally or as the
    per-shard body inside :func:`_paged_attend_sharded`.

    ``use_kernel=False`` is the jnp gather conformance reference (kept
    verbatim from the PR 5 decode path); ``use_kernel=True`` walks the
    table block-by-block via ``kernels.ops.paged_attention`` so HBM
    reads scale with live tokens.  The two paths differ on *inactive*
    lanes (the kernel returns exact zeros, the gather computes garbage)
    — both are discarded, only per-request tokens are compared."""
    from ..kernels import ops as kernel_ops

    B = q_heads.shape[0]
    nb, bs = cache_k.shape[0], cache_k.shape[2]
    blk = block_table[jnp.arange(B), pos // bs]  # (B,) pool block ids
    if active is not None:
        blk = jnp.where(active, blk, nb)  # OOB => write drops
    cache_k = cache_k.at[blk, :, pos % bs].set(k_row.astype(cache_k.dtype), mode="drop")
    cache_v = cache_v.at[blk, :, pos % bs].set(v_row.astype(cache_v.dtype), mode="drop")
    qh = q_heads.reshape(B, n_kv, -1, head_dim)
    if use_kernel:
        pos_eff = pos if active is None else jnp.where(active, pos, -1)
        out = kernel_ops.paged_attention(
            qh, cache_k, cache_v, block_table, pos_eff,
            window=window, use_pallas=True,
        ).astype(x_dtype)
        return out, cache_k, cache_v
    keys, vals = _pool_gather(cache_k, cache_v, block_table, n_kv, head_dim)
    q5 = (qh * (head_dim**-0.5))[:, None]  # (B, 1, K, G, d)
    s = _gqa_scores(q5, keys.astype(x_dtype))  # (B, K, G, 1, L)
    kpos = jnp.arange(keys.shape[1])
    valid = kpos[None, :] <= pos[:, None]
    if window is not None:
        valid &= (pos[:, None] - kpos[None, :]) < window
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = _gqa_combine(w, vals.astype(x_dtype), x_dtype)  # (B, 1, K*G*d)
    return out.reshape(B, n_kv, -1, head_dim), cache_k, cache_v


def _paged_attend_sharded(
    mesh, q_heads, k_row, v_row, cache_k, cache_v, block_table, pos, active, *,
    n_kv: int, head_dim: int, window: Optional[int], use_kernel: bool, x_dtype,
):
    """shard_map the paged update+attend: lanes and their pool blocks
    co-shard over the data axes, so each shard scatters into and gathers
    out of only its LOCAL pool slice — the pool is never all-gathered
    (GSPMD would do exactly that at the opaque Pallas call, and pays a
    cross-shard gather even on the jnp path).

    Requires lanes and blocks to shard over the *same* axes
    (``dist.sharding.block_table_spec``); the allocator grants lane b's
    blocks from lane b's shard range (``BlockAllocator(n_shards=D)``),
    so global->local id translation is a subtraction.  Stale table
    entries of other shards clip into the local range and are masked by
    the causal bound like any stale entry.  Returns None when lanes and
    blocks do not co-shard (caller falls back to the GSPMD path)."""
    from ..dist import sharding as shardrules
    from jax.sharding import PartitionSpec as P

    B = q_heads.shape[0]
    nb = cache_k.shape[0]
    pool_spec = shardrules.paged_block_spec(cache_k.shape, mesh)
    blk_ax, kv_ax = pool_spec[0], pool_spec[1]
    lane_ax = shardrules.dp_axes(mesh, B)
    if blk_ax is None or lane_ax != blk_ax:
        return None

    def _axsize(ax):
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= int(mesh.shape[a])
        return n

    local_nb = nb // _axsize(blk_ax)
    kv_local = n_kv // _axsize(kv_ax) if kv_ax is not None else n_kv
    q4 = q_heads.reshape(B, n_kv, -1, head_dim)
    if active is None:
        active = jnp.ones((B,), bool)

    def _shard_offset():
        axes = blk_ax if isinstance(blk_ax, tuple) else (blk_ax,)
        idx = jnp.int32(0)
        for a in axes:
            idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
        return idx * local_nb

    def local(qh, kr, vr, ck, cv, tbl, po, act):
        off = _shard_offset()
        tbl_l = jnp.clip(tbl - off, 0, local_nb - 1)
        return _paged_update_attend(
            qh, kr, vr, ck, cv, tbl_l, po, act, n_kv=kv_local,
            head_dim=head_dim, window=window, use_kernel=use_kernel,
            x_dtype=x_dtype,
        )

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(
            P(lane_ax, kv_ax, None, None), P(lane_ax, kv_ax, None),
            P(lane_ax, kv_ax, None), pool_spec, pool_spec,
            P(lane_ax, None), P(lane_ax), P(lane_ax),
        ),
        out_specs=(P(lane_ax, kv_ax, None, None), pool_spec, pool_spec),
        check_vma=False,
    )
    return f(q4, k_row, v_row, cache_k, cache_v, block_table, pos, active)


def decode_attention(
    p: Params,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[int] = None,
    active: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
    paged_kernel: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode.  x: (B, 1, D); cache_[kv]: (B, Smax, K, d);
    pos: scalar int32 current position, or a (B,) int32 vector of
    per-slot positions (continuous batching: each lane of the batch is an
    independent request at its own depth — RoPE, the causal mask and the
    cache write all use that lane's position).  ``active`` (per-slot path
    only): (B,) bool; inactive lanes keep their cache row untouched —
    required when prefilling lanes interleave with the pooled decode step
    (their row ``pos`` holds a real prompt key the decode's garbage write
    would otherwise clobber).

    ``block_table`` switches the cache to PAGED layout: cache_[kv] is a
    global pool of fixed-size blocks ``(n_blocks, K, block_size, d)``
    shared by every lane, and ``block_table`` is (B, blocks_per_lane)
    int32 mapping each lane's logical block index to its pool block.
    Lane b's logical row ``r`` lives at ``[table[b, r // bs], :, r % bs]``;
    the decode write scatters through the table (inactive lanes are
    redirected to the out-of-bounds block ``n_blocks`` so their writes
    drop — an inactive lane's table row may hold stale or unallocated
    entries that now belong to another lane) and the attention read
    gathers the lane's logical view back out of the pool.  Unallocated /
    stale table entries are harmless on the read side: their rows sit
    beyond the lane's position, so the causal mask zeroes them exactly.
    Requires per-slot ``pos``.  Returns (out, new_k, new_v) with new_k /
    new_v in the pool layout.

    ``paged_kernel=True`` replaces the full-pool-view gather read with
    the Pallas block-table-walking kernel (``kernels.paged_attention``):
    per-step HBM reads scale with each lane's live tokens instead of
    blocks_per_lane x block_size.  The gather path stays the conformance
    reference.  Under ``common.paged_shard_mesh`` (set by the scheduler
    when block tables are data-sharded) either path runs shard-local
    inside shard_map — the pool is never all-gathered."""
    B = x.shape[0]
    G = n_heads // n_kv
    q, k, v = _qkv(p, x, n_heads, n_kv, head_dim)
    per_slot = jnp.ndim(pos) == 1
    paged = block_table is not None
    if paged and not per_slot:
        raise ValueError("paged decode needs per-slot positions (a slot pool)")
    posb = pos[:, None] if per_slot else jnp.full((B, 1), pos)
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)
    if paged:
        args = (q[:, 0], k[:, 0], v[:, 0], cache_k, cache_v, block_table, pos, active)
        kw = dict(n_kv=n_kv, head_dim=head_dim, window=window,
                  use_kernel=paged_kernel, x_dtype=x.dtype)
        mesh = paged_mesh()
        res = _paged_attend_sharded(mesh, *args, **kw) if mesh is not None else None
        if res is None:  # unsharded, or lanes/blocks don't co-shard
            res = _paged_update_attend(*args, **kw)
        out, cache_k, cache_v = res
        out = out.reshape(B, 1, -1)
        return dense_apply(out, p["wo"]), cache_k, cache_v
    if per_slot:
        bidx = jnp.arange(B)
        k_row, v_row = k[:, 0].astype(cache_k.dtype), v[:, 0].astype(cache_v.dtype)
        if active is not None:
            k_row = jnp.where(active[:, None, None], k_row, cache_k[bidx, pos])
            v_row = jnp.where(active[:, None, None], v_row, cache_v[bidx, pos])
        cache_k = cache_k.at[bidx, pos].set(k_row)
        cache_v = cache_v.at[bidx, pos].set(v_row)
        keys, vals = cache_k, cache_v
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), pos, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), pos, axis=1)
        keys, vals = cache_k, cache_v
    q = q.reshape(B, 1, n_kv, G, head_dim) * (head_dim**-0.5)
    s = _gqa_scores(q, keys.astype(x.dtype))  # (B, K, G, 1, Smax)
    kpos = jnp.arange(keys.shape[1])
    valid = kpos[None, :] <= posb  # (B, Smax) or (B-broadcast, Smax)
    if window is not None:
        valid &= (posb - kpos[None, :]) < window
    valid = jnp.broadcast_to(valid, (B, keys.shape[1]))
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = _gqa_combine(w, vals.astype(x.dtype), x.dtype)
    return dense_apply(out, p["wo"]), cache_k, cache_v


def decode_attention_cache(
    p: Params,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    pos: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[int] = None,
    ring: bool = False,
    active: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
    paged_kernel: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against either a full-length cache or a ring buffer.

    Ring buffer (``ring=True``, sliding-window layers): the cache holds the
    last ``Wc = cache_k.shape[1]`` entries; position ``p`` lives in slot
    ``p % Wc``.  Keys are stored post-RoPE, so only absolute positions
    matter, which slot ``s`` encodes as ``p_s = pos - ((pos - s) mod Wc)``.
    This caps the long-context cache of local layers at the window size —
    the difference between 16 GB and 64 MB per local layer at 500k.

    ``pos`` may be a scalar or a (B,) per-slot vector (continuous
    batching) — with a vector, each lane writes its own ring slot and
    masks against its own absolute positions.

    ``block_table`` (full-length caches only) selects the paged pool
    layout — see :func:`decode_attention`.  Ring buffers are already
    bounded at the window size, so they never page and ignore it.
    """
    if not ring:
        return decode_attention(
            p, x, cache_k, cache_v, pos, n_heads=n_heads, n_kv=n_kv,
            head_dim=head_dim, rope_theta=rope_theta, window=window,
            active=active, block_table=block_table, paged_kernel=paged_kernel,
        )
    B = x.shape[0]
    Wc = cache_k.shape[1]
    G = n_heads // n_kv
    q, k, v = _qkv(p, x, n_heads, n_kv, head_dim)
    per_slot = jnp.ndim(pos) == 1
    posb = pos[:, None] if per_slot else jnp.full((B, 1), pos)
    q = apply_rope(q, posb, rope_theta)
    k = apply_rope(k, posb, rope_theta)
    if per_slot:
        bidx = jnp.arange(B)
        lane_slot = jnp.mod(pos, Wc)  # (B,)
        k_row, v_row = k[:, 0].astype(cache_k.dtype), v[:, 0].astype(cache_v.dtype)
        if active is not None:
            k_row = jnp.where(active[:, None, None], k_row, cache_k[bidx, lane_slot])
            v_row = jnp.where(active[:, None, None], v_row, cache_v[bidx, lane_slot])
        cache_k = cache_k.at[bidx, lane_slot].set(k_row)
        cache_v = cache_v.at[bidx, lane_slot].set(v_row)
    else:
        slot = jnp.mod(pos, Wc)
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k.astype(cache_k.dtype), slot, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v.astype(cache_v.dtype), slot, axis=1)
    q = q.reshape(B, 1, n_kv, G, head_dim) * (head_dim**-0.5)
    s = _gqa_scores(q, cache_k.astype(x.dtype))  # (B, K, G, 1, Wc)
    slots = jnp.arange(Wc)
    abs_pos = posb - jnp.mod(posb - slots[None, :], Wc)  # (B, Wc) / (1, Wc)
    valid = abs_pos >= 0
    if window is not None and window < Wc:
        valid &= (posb - abs_pos) < window
    valid = jnp.broadcast_to(valid, (B, Wc))
    s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = _gqa_combine(w, cache_v.astype(x.dtype), x.dtype)
    return dense_apply(out, p["wo"]), cache_k, cache_v


def prefill_chunk_attention(
    p: Params,
    x: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    start: jax.Array,
    n_valid: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
    rope_theta: float,
    window: Optional[int] = None,
    ring: bool = False,
    scores_dtype=jnp.float32,
    block_table: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked prefill: C prompt-token queries per lane against the lane's
    own rows of the pooled cache.

    ``x``: (B, C, D) — one fixed-size chunk per lane; ``start``: (B,) the
    chunk's first absolute position; ``n_valid``: (B,) how many of the C
    tokens are real.  Trailing pad tokens produce garbage rows/outputs
    that are never read: pad cache rows sit beyond the lane's position
    and are overwritten by the next chunk or the first decode write, and
    the scheduler discards pad logits.  Lanes not prefilling pass
    ``n_valid = 0`` and (non-ring path) ``start = max_len`` so every one
    of their writes is out of bounds and drops.

    Full-length caches (``ring=False``) write the chunk's K/V first and
    attend against the updated cache — rows ``<= start + i`` are exactly
    the lane's processed prefix, so the causal mask alone confines query
    ``i`` to real keys.  Ring buffers (``ring=True``): a chunk longer
    than the ring would overwrite keys its own queries still need, so
    scores run over [chunk K/V ; pre-chunk ring] instead, and the ring is
    then rebuilt by gather: slot ``s``'s new content is the *latest* valid
    chunk position congruent to it, or the old content if the chunk never
    reached that slot.

    ``block_table`` (full-length caches only) switches the cache to the
    paged pool layout of :func:`decode_attention`: writes scatter each
    real chunk token through the lane's block table (pad tokens and
    positions past the lane's allocation are redirected out of bounds and
    drop), and scores run over the lane-logical gather view of the pool.
    The caller must have allocated blocks covering rows
    [start, start + n_valid) before dispatch.  Returns (out, new_k,
    new_v)."""
    B, C, _ = x.shape
    G = n_heads // n_kv
    q, k, v = _qkv(p, x, n_heads, n_kv, head_dim)
    qpos = start[:, None] + jnp.arange(C)[None, :]  # (B, C)
    q = apply_rope(q, qpos, rope_theta)
    k = apply_rope(k, qpos, rope_theta)
    qs = q.reshape(B, C, n_kv, G, head_dim) * (head_dim**-0.5)
    neg = jnp.asarray(NEG_INF, scores_dtype)
    if not ring:
        if block_table is not None:
            nb, bs = cache_k.shape[0], cache_k.shape[2]
            nb_lane = block_table.shape[1]
            bi = jnp.clip(qpos // bs, 0, nb_lane - 1)  # (B, C) logical blocks
            blk = jnp.take_along_axis(block_table, bi, axis=1)
            # only real tokens within the lane's table reach the pool;
            # pads and the idle lanes' start=max_len sentinel rows drop
            ok = (jnp.arange(C)[None, :] < n_valid[:, None]) & (qpos < nb_lane * bs)
            blk = jnp.where(ok, blk, nb)
            cache_k = cache_k.at[blk, :, qpos % bs].set(k.astype(cache_k.dtype), mode="drop")
            cache_v = cache_v.at[blk, :, qpos % bs].set(v.astype(cache_v.dtype), mode="drop")
            keys, vals = _pool_gather(cache_k, cache_v, block_table, n_kv, head_dim)
        else:
            bidx = jnp.arange(B)[:, None]
            cache_k = cache_k.at[bidx, qpos].set(k.astype(cache_k.dtype), mode="drop")
            cache_v = cache_v.at[bidx, qpos].set(v.astype(cache_v.dtype), mode="drop")
            keys, vals = cache_k, cache_v
        s = _gqa_scores(qs, keys.astype(x.dtype), scores_dtype)  # (B,K,G,C,Smax)
        kpos = jnp.arange(keys.shape[1])
        valid = kpos[None, None, :] <= qpos[:, :, None]  # (B, C, Smax)
        if window is not None:
            valid &= (qpos[:, :, None] - kpos[None, None, :]) < window
        s = jnp.where(valid[:, None, None], s, neg)
        s = s - jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
        w = jax.nn.softmax(s.astype(scores_dtype), axis=-1)
        out = _gqa_combine(w, vals.astype(x.dtype), x.dtype)
        return dense_apply(out, p["wo"]), cache_k, cache_v

    Wc = cache_k.shape[1]
    ci = jnp.arange(C)
    # intra-chunk keys: plain causal (+window) on chunk-relative offsets
    s1 = _gqa_scores(qs, k, scores_dtype)  # (B,K,G,C,C)
    m1 = ci[:, None] >= ci[None, :]
    if window is not None:
        m1 &= (ci[:, None] - ci[None, :]) < window
    s1 = jnp.where(m1[None, None, None], s1, neg)
    # pre-chunk ring keys: slot s holds absolute position
    # r_s = (start-1) - ((start-1-s) mod Wc) — the latest processed
    # position congruent to s (continuity invariant of the rebuild below);
    # r_s < 0 means the lane never reached that slot (stale content).
    slots = jnp.arange(Wc)
    r = (start[:, None] - 1) - jnp.mod(start[:, None] - 1 - slots[None, :], Wc)
    s2 = _gqa_scores(qs, cache_k.astype(x.dtype), scores_dtype)  # (B,K,G,C,Wc)
    m2 = jnp.broadcast_to((r >= 0)[:, None, :], (B, C, Wc))
    if window is not None:
        m2 &= (qpos[:, :, None] - r[:, None, :]) < window
    s2 = jnp.where(m2[:, None, None], s2, neg)
    s = jnp.concatenate([s1, s2], axis=-1)  # (B,K,G,C,C+Wc)
    s = s - jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    w = jax.nn.softmax(s.astype(scores_dtype), axis=-1)
    v_all = jnp.concatenate([v, cache_v.astype(x.dtype)], axis=1)
    out = _gqa_combine(w, v_all, x.dtype)
    # ring rebuild (gather-select, deterministic where scatter-with-
    # duplicates is not): slot s's final occupant is the latest valid
    # chunk position congruent to it, else the old content survives.
    last = start + n_valid - 1  # (B,)
    p_s = last[:, None] - jnp.mod(last[:, None] - slots[None, :], Wc)  # (B, Wc)
    in_chunk = p_s >= start[:, None]  # implies p_s < start + n_valid
    i_s = jnp.clip(p_s - start[:, None], 0, C - 1)
    k_sel = jnp.take_along_axis(k.astype(cache_k.dtype), i_s[..., None, None], axis=1)
    v_sel = jnp.take_along_axis(v.astype(cache_v.dtype), i_s[..., None, None], axis=1)
    cache_k = jnp.where(in_chunk[..., None, None], k_sel, cache_k)
    cache_v = jnp.where(in_chunk[..., None, None], v_sel, cache_v)
    return dense_apply(out, p["wo"]), cache_k, cache_v


def cross_attention(
    p: Params,
    x: jax.Array,
    kv_src: jax.Array,
    *,
    n_heads: int,
    n_kv: int,
    head_dim: int,
) -> jax.Array:
    """Unmasked cross-attention: x (B,S,D) queries attend to kv_src (B,T,D).

    Used for the VLM image layers (kv_src = precomputed patch embeddings,
    identical at train and decode time — no cache update needed)."""
    B, S, _ = x.shape
    G = n_heads // n_kv
    q = dense_apply(x, p["wq"]).reshape(B, S, n_kv, G, head_dim) * (head_dim**-0.5)
    k = dense_apply(kv_src, p["wk"]).reshape(B, -1, n_kv, head_dim)
    v = dense_apply(kv_src, p["wv"]).reshape(B, -1, n_kv, head_dim)
    s = _gqa_scores(q, k)
    w = jax.nn.softmax(s, axis=-1)
    out = _gqa_combine(w, v, x.dtype)
    return dense_apply(out, p["wo"])
