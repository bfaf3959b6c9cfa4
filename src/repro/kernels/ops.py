"""Jitted public wrappers: Pallas on TPU, interpret-mode Pallas or the
pure-jnp ref elsewhere.  These are the entry points the rest of the
system calls (serve engine, regularizer fast path, prefill attention).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.packing import PackedWeight, scale_row
from . import ref
from .bgl_norm import bgl_sumsq_pallas
from .bitserial_matmul import bitserial_matmul_pallas, bitserial_matmul_pallas_dyn
from .flash_attention import flash_attention_pallas
from .paged_attention import paged_attention_pallas


class ShardFallbackWarning(UserWarning):
    """``bitserial_matmul_sharded`` fell back to the unsharded call, so
    the packed bytes are gathered at the kernel.  A category of its own
    so a path that must stay sharded can turn it into an error."""


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def bitserial_matmul(
    x: jax.Array, pw: PackedWeight, *, active_planes=None,
    use_pallas: bool | None = None, interpret: bool | None = None, out_dtype=None,
) -> jax.Array:
    """x (..., K) @ packed weight (K, N) with on-the-fly dequantisation.

    The per-group scale row is applied as an output-column epilogue
    (inside the Pallas kernel's final k step; same formula on the ref
    path), so per-group exports dequantise exactly on both backends.

    ``active_planes`` — a *runtime* (not compiled) int32 scalar — keeps
    only the ``a`` most significant planes in the accumulation; the
    dropped planes' shift folds into the epilogue as an exact power of
    two, so the output is bitwise-equal to the static path over
    ``core.packing.truncate_packed(pw, a)`` while ONE compiled program
    serves every precision level (the spec-decode draft dispatch).
    ``None`` keeps the fully static path untouched.

    ``out_dtype`` (default ``x.dtype``) is the dtype the float32
    accumulator is rounded to.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    if use_pallas:
        interpret = (not _on_tpu()) if interpret is None else interpret
        M, K = x2.shape
        N = pw.sign.shape[-1]
        # Rows pad to a multiple of the (8-aligned) row tile, so every
        # M — decode's n_slots, a prefill chunk's n_slots * C — gets a
        # legal, bounded TPU block.
        bm = min(128, -(-M // 8) * 8)
        x2 = jnp.pad(x2, ((0, -(-M // bm) * bm - M), (0, 0)))
        bn = 128 if N % 128 == 0 else N
        bk = 512 if K % 512 == 0 else (128 if K % 128 == 0 else K)
        if active_planes is None:
            out = bitserial_matmul_pallas(
                x2, pw.planes, pw.sign, scale_row(pw.scale, N), n_bits=pw.n_bits,
                denom_bits=pw.denom_bits, block_m=bm, block_n=bn, block_k=bk,
                out_dtype=out_dtype, interpret=interpret,
            )
        else:
            out = bitserial_matmul_pallas_dyn(
                x2, pw.planes, pw.sign, scale_row(pw.scale, N),
                jnp.asarray(active_planes, jnp.int32).reshape(1, 1),
                n_bits=pw.n_bits, denom_bits=pw.denom_bits,
                block_m=bm, block_n=bn, block_k=bk, out_dtype=out_dtype,
                interpret=interpret,
            )
        out = out[:M]
    else:
        out = ref.bitserial_matmul_ref(
            x2, pw.planes, pw.sign, pw.scale, pw.n_bits,
            denom_bits=pw.denom_bits, active_planes=active_planes,
        ).astype(out_dtype or x.dtype)
    return out.reshape(*lead, -1)


def _axis_size(mesh, ax) -> int:
    if ax is None:
        return 1
    axes = ax if isinstance(ax, tuple) else (ax,)
    size = 1
    for a in axes:
        size *= int(mesh.shape[a])
    return size


def bitserial_matmul_sharded(
    x: jax.Array,
    pw: PackedWeight,
    mesh,
    *,
    active_planes=None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """shard_map-wrapped packed matmul: each shard runs the bitserial
    kernel on its LOCAL planes/sign/scale block and a psum over the
    contraction axis stitches the result.

    The Pallas bitserial kernel lowers to a custom call GSPMD cannot
    partition — without this wrapper a sharded ``planes`` operand would
    be all-gathered at the call.  ``pw.kn_spec`` (set by
    ``dist.sharding.annotate_packed_specs``) names the mesh axes of the
    trailing (K, N) weight axes; ``x`` is resharded so its contraction
    axis lines up with the weight's K shards, partial products are
    psum'd over the K axis, and the output comes back sharded over the
    weight's N axis (col-parallel) or the data axis (row-parallel) —
    the usual Megatron stitching, with packed bytes staying local.

    Falls back to the unsharded call when the annotation or the shapes
    make local blocks ill-defined (no K/N sharding, K not byte-aligned
    across shards, or a group-scale row that does not divide over the N
    shards).
    """
    k_ax, n_ax = pw.kn_spec if pw.kn_spec is not None else (None, None)
    K8, N = pw.sign.shape[-2:]
    dk, dn = _axis_size(mesh, k_ax), _axis_size(mesh, n_ax)
    s = jnp.asarray(pw.scale)
    shardable = (
        pw.planes.ndim == 3  # 2D weight (scan has already sliced any stack)
        and (dk > 1 or dn > 1)
        and pw.k == K8 * 8  # pad rows would straddle the shard boundary
        and K8 % dk == 0
        and N % dn == 0
        and (s.ndim == 0 or s.shape[-1] == 1 or s.shape[-1] % dn == 0)
    )
    if not shardable:
        # The byte tensors may well BE mesh-sharded (dist.sharding no
        # longer replicates them) — falling back to the plain call hands
        # them to GSPMD, which must all-gather them at the opaque Pallas
        # custom call, forfeiting the per-device packed HBM win.  Warn
        # loudly (once per trace) instead of regressing silently.
        import warnings

        warnings.warn(
            f"bitserial_matmul_sharded: falling back to the unsharded packed "
            f"matmul (kn_spec={pw.kn_spec}, sign shape {pw.sign.shape}, "
            f"scale shape {tuple(s.shape)}, k={pw.k}) — local shard blocks "
            "are ill-defined (indivisible K8/N/scale groups or padded K); "
            "packed bytes will be gathered at the kernel call",
            ShardFallbackWarning, stacklevel=2,
        )
        return bitserial_matmul(x, pw, active_planes=active_planes,
                                use_pallas=use_pallas, interpret=interpret)

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if s.ndim == 0:
        s_spec = P()
    elif s.shape[-1] > 1 and dn > 1:  # group row splits evenly (checked above)
        s_spec = P(None, n_ax)
    else:
        s_spec = P(None, None)
    spec_pw = dataclasses.replace(
        pw, planes=P(None, k_ax, n_ax), sign=P(k_ax, n_ax), scale=s_spec
    )

    # Partial products stay float32 through the psum and round to
    # x.dtype once, as the unsharded kernel rounds its float32
    # accumulator once: sharded and single-device outputs then agree to
    # the last bit but for rare rounding ties.
    if active_planes is None:
        def local(xl, pwl):
            y = bitserial_matmul(xl, pwl, use_pallas=use_pallas, interpret=interpret,
                                 out_dtype=jnp.float32)
            if k_ax is not None:
                y = jax.lax.psum(y, k_ax)
            return y

        f = jax.shard_map(local, mesh=mesh, in_specs=(P(None, k_ax), spec_pw),
                          out_specs=P(None, n_ax), check_vma=False)
        return f(x2, pw).astype(x.dtype).reshape(*lead, -1)

    # Runtime active-plane count: a replicated (1, 1) scalar operand —
    # every shard masks the same planes of its LOCAL packed bytes, so
    # the packed sharding (and the psum stitching) is unchanged.
    def local_dyn(xl, pwl, al):
        y = bitserial_matmul(xl, pwl, active_planes=al, use_pallas=use_pallas,
                             interpret=interpret, out_dtype=jnp.float32)
        if k_ax is not None:
            y = jax.lax.psum(y, k_ax)
        return y

    f = jax.shard_map(
        local_dyn, mesh=mesh,
        in_specs=(P(None, k_ax), spec_pw, P(None, None)),
        out_specs=P(None, n_ax), check_vma=False,
    )
    a2 = jnp.asarray(active_planes, jnp.int32).reshape(1, 1)
    return f(x2, pw, a2).astype(x.dtype).reshape(*lead, -1)


def bgl_sumsq(x: jax.Array, *, use_pallas: bool | None = None, interpret: bool | None = None):
    """Per-row sum of squares; rows = (bit, group) pairs."""
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    if not use_pallas:
        return ref.bgl_sumsq_ref(x)
    interpret = (not _on_tpu()) if interpret is None else interpret
    R, C = x.shape
    br = 8 if R % 8 == 0 else 1
    bc = 4096 if C % 4096 == 0 else (512 if C % 512 == 0 else C)
    return bgl_sumsq_pallas(x, block_r=br, block_c=bc, interpret=interpret)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """(BH, S, d) flash attention; GQA callers broadcast kv beforehand."""
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    interpret = (not _on_tpu()) if interpret is None else interpret
    S = q.shape[1]
    bq = 128 if S % 128 == 0 else S
    bk = 128 if S % 128 == 0 else S
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, block_q=bq, block_k=bk, interpret=interpret
    )


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    pos: jax.Array,
    *,
    window: int | None = None,
    sm_scale: float | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Paged decode attention: q (B, KV, G, d) against the block pools.

    The Pallas path walks each lane's block table in place so HBM reads
    scale with live tokens; the ref path gathers the full logical view
    (exactly what ``models.attention`` does on the gather backend) and
    is the conformance oracle.  ``pos < 0`` lanes return exact zeros on
    both paths.
    """
    use_pallas = _on_tpu() if use_pallas is None else use_pallas
    if not use_pallas:
        return ref.paged_attention_ref(
            q, k_pool, v_pool, block_table, pos, window=window, sm_scale=sm_scale
        )
    interpret = (not _on_tpu()) if interpret is None else interpret
    return paged_attention_pallas(
        q, k_pool, v_pool, block_table, pos,
        window=window, sm_scale=sm_scale, interpret=interpret,
    )
