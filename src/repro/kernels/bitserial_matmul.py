"""Pallas TPU kernel: fused unpack -> dequantise -> MXU matmul over
bit-plane-packed weights (the serving hot path of BSQ).

Weights live in HBM as ``planes (n_bits, K/8, N) uint8`` + ``sign
(K/8, N) uint8`` + a per-output-column scale row ``(1, N) f32``
(sign-magnitude layout from core/packing.py; per-group scale rows are
expanded to per-column by ops.py).  Per (m, n, k) grid step the kernel:

  1. DMAs an x tile (bm, bk) and the packed tiles (n_bits, bk/8, bn),
     (bk/8, bn) into VMEM  — HBM traffic for weights is (n_bits+1)/16 of
     a bf16 weight load, which is the whole point: decode-time matmuls
     are HBM-bandwidth-bound, so wall time scales with the *mixed
     precision* BSQ found;
  2. widens the bytes to int32 and takes bit ``j`` of every byte as
     ``(p >> j) & 1`` — the weight rows ``8r + j`` — so the signed
     integer tile is built on whole (bk/8, bn) tiles with shifts (VPU)
     and written to a VMEM scratch in bit-major row order; ``x`` arrives
     with its columns permuted to the same order (``_bit_major``), so
     no sublane-splitting reshape or uint8 float cast is needed;
  3. accumulates ``x_tile @ w_tile`` into an f32 VMEM scratch; the final
     k step applies the epilogue ``acc * scale_row / (2^n - 1)`` — the
     per-group scales ride in the (1, bn) scale tile, so dequantisation
     stays exact even when groups disagree (no global mean scale).

Validated against ref.bitserial_matmul_ref in interpret mode (tests
sweep shapes/dtypes/n_bits/scale groupings).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _weight_tile(planes_ref, sign_ref, w_ref, lo=None):
    """Unpack the (n_bits, bk/8, bn) plane bytes and (bk/8, bn) sign bytes
    into the signed integer weight tile, written to ``w_ref`` (bk, bn) in
    BIT-MAJOR row order: row ``j*bk/8 + r`` holds weight row ``8r + j``.

    Bytes widen to int32 first and bit ``j`` comes out as
    ``(p >> j) & 1`` on whole (bk/8, bn) tiles, so the unpack needs no
    uint8 float cast and no sublane-splitting reshape — the caller feeds
    ``x`` in the matching permuted column order instead.  ``lo`` (traced
    int32, dynamic kernel) drops the ``lo`` least significant planes:
    ``q >> lo`` keeps exactly bits ``[lo, n)`` reweighted to ``2^(b-lo)``.
    """
    n_bits, bk8, _ = planes_ref.shape
    planes = [planes_ref[b].astype(jnp.int32) for b in range(n_bits)]
    sign = sign_ref[...].astype(jnp.int32)
    for j in range(8):
        mag = (planes[0] >> j) & 1
        for b in range(1, n_bits):
            mag = mag | (((planes[b] >> j) & 1) << b)
        if lo is not None:
            mag = mag >> lo
        w = jnp.where(((sign >> j) & 1) == 1, -mag, mag)
        w_ref[j * bk8:(j + 1) * bk8, :] = w.astype(jnp.float32).astype(w_ref.dtype)


def _kernel(x_ref, planes_ref, sign_ref, scale_ref, out_ref, acc_ref, w_ref, *,
            denom_bits: int, nsteps_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _weight_tile(planes_ref, sign_ref, w_ref)
    acc_ref[...] += jax.lax.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nsteps_k - 1)
    def _finish():
        denom = 2.0**denom_bits - 1.0
        s = scale_ref[...] * (1.0 / denom)  # (1, bn) f32 epilogue row
        out_ref[...] = (acc_ref[...] * s).astype(out_ref.dtype)


def _kernel_dyn(active_ref, x_ref, planes_ref, sign_ref, scale_ref, out_ref, acc_ref,
                w_ref, *, n_bits: int, denom_bits: int, nsteps_k: int):
    """Runtime-active-plane variant: ``active_ref`` is a (1, 1) int32 SMEM
    scalar selecting the ``a`` most significant planes.  The magnitude
    is shifted right by the ``lo = n - a`` dropped planes and the shift
    folds into the epilogue as ``2^lo`` — a power of two, so the output
    is bitwise-equal to the static kernel over
    ``core.packing.truncate_packed(pw, a)``.  DMA traffic is unchanged
    (every plane tile still lands in VMEM); the win this kernel buys is
    ONE compiled program serving every precision level.
    """
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    lo = n_bits - jnp.clip(active_ref[0, 0], 1, n_bits)  # first live plane
    _weight_tile(planes_ref, sign_ref, w_ref, lo=lo)
    acc_ref[...] += jax.lax.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nsteps_k - 1)
    def _finish():
        denom = 2.0**denom_bits - 1.0
        # (scale * 2^lo) first — exact — then the reciprocal multiply,
        # the same rounding sequence as the static kernel's epilogue.
        pow_lo = (jnp.ones(scale_ref.shape, jnp.int32) << lo).astype(jnp.float32)
        s = (scale_ref[...] * pow_lo) * (1.0 / denom)
        out_ref[...] = (acc_ref[...] * s).astype(out_ref.dtype)


def _bit_major(x: jax.Array, block_k: int) -> jax.Array:
    """Permute x's columns within each K tile to the weight tile's
    bit-major row order: column ``t*bk + j*bk/8 + r`` <- ``t*bk + 8r + j``.
    (M, K) at decode is small, so this XLA transpose is cheap next to the
    weight bytes it spares the kernel from reshaping."""
    M, K = x.shape
    return (x.reshape(M, K // block_k, block_k // 8, 8)
            .transpose(0, 1, 3, 2).reshape(M, K))


def _call(kern, x, planes, sign, scale, lead_operands, lead_specs, *,
          block_m, block_n, block_k, out_dtype, interpret):
    M, K = x.shape
    N = sign.shape[-1]
    block_m = min(block_m, M)
    block_n = min(block_n, N)
    block_k = min(block_k, K)
    assert K % block_k == 0 and block_k % 8 == 0, (K, block_k)
    assert M % block_m == 0 and N % block_n == 0, (M, N, block_m, block_n)
    assert scale.shape == (1, N), (scale.shape, N)
    nk = K // block_k
    return pl.pallas_call(
        functools.partial(kern, nsteps_k=nk),
        grid=(M // block_m, N // block_n, nk),
        in_specs=[
            *lead_specs,
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((planes.shape[0], block_k // 8, block_n), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((block_k // 8, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype or x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_k, block_n), x.dtype),
        ],
        interpret=interpret,
    )(*lead_operands, _bit_major(x, block_k), planes, sign, scale.astype(jnp.float32))


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "denom_bits", "block_m", "block_n", "block_k", "out_dtype",
                     "interpret"),
)
def bitserial_matmul_pallas(
    x: jax.Array,  # (M, K)
    planes: jax.Array,  # (n_bits, K/8, N) uint8
    sign: jax.Array,  # (K/8, N) uint8
    scale: jax.Array,  # (1, N) f32 per-output-column scale row
    *,
    n_bits: int,
    denom_bits: int | None = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    kern = functools.partial(
        _kernel, denom_bits=n_bits if denom_bits is None else denom_bits)
    return _call(kern, x, planes, sign, scale, (), (), block_m=block_m,
                 block_n=block_n, block_k=block_k, out_dtype=out_dtype,
                 interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("n_bits", "denom_bits", "block_m", "block_n", "block_k", "out_dtype",
                     "interpret"),
)
def bitserial_matmul_pallas_dyn(
    x: jax.Array,  # (M, K)
    planes: jax.Array,  # (n_bits, K/8, N) uint8
    sign: jax.Array,  # (K/8, N) uint8
    scale: jax.Array,  # (1, N) f32 per-output-column scale row
    active: jax.Array,  # (1, 1) int32 runtime active-plane count
    *,
    n_bits: int,
    denom_bits: int | None = None,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """One compiled program for every precision level: ``active`` rides
    in SMEM as a runtime scalar, so draft (few-plane) and full-precision
    dispatches hit the same executable."""
    kern = functools.partial(
        _kernel_dyn, n_bits=n_bits,
        denom_bits=n_bits if denom_bits is None else denom_bits)
    return _call(
        kern, x, planes, sign, scale,
        (jnp.asarray(active, jnp.int32).reshape(1, 1),),
        (pl.BlockSpec((1, 1), lambda i, j, k: (0, 0), memory_space=pltpu.SMEM),),
        block_m=block_m, block_n=block_n, block_k=block_k, out_dtype=out_dtype,
        interpret=interpret)
