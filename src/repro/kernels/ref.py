"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.packing import scale_row, unpack_bits_axis0


def bitserial_matmul_ref(x, planes, sign, scale, n_bits: int,
                         denom_bits: int | None = None, active_planes=None):
    """x (M,K) @ dequant(planes, sign) * scale_row / (2^denom_bits - 1).

    ``scale`` may be a scalar or a per-group ``(1, G)`` row (G dividing
    N); either way it is applied as an output-column epilogue, matching
    the Pallas kernel's final-k step exactly.  ``denom_bits`` (default
    ``n_bits``) carries a truncated view's original denominator.

    ``active_planes`` — a *runtime* int32 scalar — restricts the
    accumulation to the ``a`` most significant planes: the plane loop
    is statically unrolled with a per-plane mask (a dynamic-bound
    ``fori_loop`` defeats XLA's unroll-and-fuse and costs ~2x per
    dispatch on CPU hosts; real plane-skipping lives in the Pallas dyn
    kernel), and the dropped planes' shift folds into the epilogue as
    ``2^(n-a)`` — a power of two.  Masked planes contribute exact
    zeros added in the same order as the truncated static path, so the
    result is BITWISE equal to running the static path on
    ``core.packing.truncate_packed(pw, a)``.
    """
    K = x.shape[1]
    denom = 2.0 ** (n_bits if denom_bits is None else denom_bits) - 1.0
    N = sign.shape[-1]
    if active_planes is None:
        mag = sum(
            unpack_bits_axis0(planes[b], K).astype(jnp.float32) * (2.0**b)
            for b in range(n_bits)
        )
        s = scale_row(scale, N) / denom
    else:
        a = jnp.clip(jnp.asarray(active_planes, jnp.int32).reshape(()), 1, n_bits)
        lo = n_bits - a  # first live plane; kept planes reweight to 2^(b-lo)
        lo_f = lo.astype(jnp.float32)
        mag = jnp.zeros((K, N), jnp.float32)
        for b in range(n_bits):
            t = unpack_bits_axis0(planes[b], K).astype(jnp.float32)
            # 0.0 for a dropped plane: t >= 0, so t * 0.0 is +0.0 and
            # the accumulation order/values match the truncated path.
            w_b = jnp.where(b >= lo, jnp.exp2(jnp.float32(b) - lo_f), 0.0)
            mag = mag + t * w_b
        # (scale * 2^(n-a)) first — exact — then the denom divide, the
        # same rounding sequence as the static truncated path.
        s = (scale_row(scale, N) * jnp.exp2(lo_f)) / denom
    sgn = 1.0 - 2.0 * unpack_bits_axis0(sign, K).astype(jnp.float32)
    w = (sgn * mag).astype(x.dtype)
    return (x @ w) * s.astype(x.dtype)


def bgl_sumsq_ref(x: jax.Array) -> jax.Array:
    """Per-row sum of squares of an (R, C) matrix, f32."""
    x = x.astype(jnp.float32)
    return jnp.sum(x * x, axis=1)


def flash_attention_ref(q, k, v, *, causal=True, window=None, sm_scale=None):
    """Naive f32 softmax attention over (BH, S, d)."""
    BH, S, d = q.shape
    if sm_scale is None:
        sm_scale = d**-0.5
    s = jnp.einsum("bqd,bkd->bqk", q, k, preferred_element_type=jnp.float32) * sm_scale
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(v.dtype), v)


def paged_attention_ref(q, k_pool, v_pool, block_table, pos, *, window=None,
                        sm_scale=None):
    """Naive f32 softmax decode attention over the block-table gather.

    ``q``: (B, KV, G, d) single-query heads (kv-major GQA layout);
    ``k_pool``/``v_pool``: the global paged pools (n_blocks, KV,
    block_size, d); ``block_table``: (B, blocks_per_lane) int32; ``pos``: (B,)
    int32 per-lane positions.  Lane b attends its lane-logical rows
    ``[0, pos[b]]`` (optionally windowed) gathered out of the pool —
    stale/unallocated table entries are masked by the causal bound
    exactly as in ``models.attention.decode_attention``.  ``pos[b] < 0``
    marks an inactive lane and yields exact zeros (the contract the
    Pallas kernel's empty accumulator meets for free).
    """
    B, KV, G, d = q.shape
    bs = k_pool.shape[2]
    L = block_table.shape[1] * bs
    if sm_scale is None:
        sm_scale = d**-0.5
    keys = k_pool[block_table].transpose(0, 1, 3, 2, 4).reshape(B, L, KV, d)
    vals = v_pool[block_table].transpose(0, 1, 3, 2, 4).reshape(B, L, KV, d)
    s = jnp.einsum("bkgd,bskd->bkgs", q.astype(jnp.float32),
                   keys.astype(jnp.float32)) * sm_scale
    kpos = jnp.arange(L)
    valid = kpos[None, :] <= pos[:, None]
    if window is not None:
        valid &= (pos[:, None] - kpos[None, :]) < window
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vals.astype(jnp.float32))
    out = jnp.where((pos >= 0)[:, None, None, None], out, 0.0)
    return out.astype(q.dtype)
