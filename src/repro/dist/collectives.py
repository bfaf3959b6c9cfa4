"""Compressed collectives: int8 + error-feedback gradient all-reduce.

The compressed-DP train step quantises each shard's gradient block to
int8 under a shared (pmax'd) scale, all-reduces the *integer* codes —
that is the on-the-wire payload, 1/4 of f32 — and dequantises once.  The
per-shard quantisation error is carried forward as an error-feedback
residual, so the bias of the compressed estimator averages out over
steps (Karimireddy et al. 2019; the substrate test checks this directly).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

PyTree = Any

_EPS = 1e-12


def quantize_int8(x: jax.Array, scale: jax.Array | None = None) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantisation: returns (codes, scale) with
    ``x ~= codes * scale`` and ``|x - deq| <= scale / 2`` elementwise."""
    x = x.astype(jnp.float32)
    if scale is None:
        scale = jnp.maximum(jnp.max(jnp.abs(x)), _EPS) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def init_residuals(tree: PyTree, n_shards: int | None = None) -> PyTree:
    """Zero error-feedback residuals matching a gradient tree (f32).

    With ``n_shards``, each leaf gains a leading shard axis — the layout
    the compressed-DP step shards over the data axis (the residual is
    genuinely per-shard state)."""
    lead = () if n_shards is None else (n_shards,)
    return jax.tree.map(lambda x: jnp.zeros(lead + tuple(x.shape), jnp.float32), tree)


def compressed_psum_ef(
    g: jax.Array, residual: jax.Array, axis: str
) -> Tuple[jax.Array, jax.Array]:
    """One leaf of the int8+EF all-reduce (inside shard_map over ``axis``).

    The scale is pmax'd across shards first, so the integer codes sum
    exactly: ``psum(int codes) * scale`` is bit-identical to summing the
    dequantised blocks, while the wire format stays 8-bit.  Returns
    (mean gradient — replicated, new local residual).
    """
    c = g.astype(jnp.float32) + residual
    scale = jax.lax.pmax(jnp.maximum(jnp.max(jnp.abs(c)), _EPS), axis) / 127.0
    q, _ = quantize_int8(c, scale)
    deq = dequantize_int8(q, scale)
    new_residual = c - deq
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
    mean = jax.lax.psum(q.astype(jnp.int32), axis).astype(jnp.float32) * scale / n
    return mean.astype(g.dtype), new_residual


def tree_compressed_psum_ef(
    grads: PyTree, residuals: PyTree, axis: str
) -> Tuple[PyTree, PyTree]:
    """Leaf-wise :func:`compressed_psum_ef`; returns (grads, residuals)."""
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residuals)
    pairs = [compressed_psum_ef(g, r, axis) for g, r in zip(flat_g, flat_r)]
    return (
        jax.tree_util.tree_unflatten(treedef, [m for m, _ in pairs]),
        jax.tree_util.tree_unflatten(treedef, [r for _, r in pairs]),
    )


# ---------------------------------------------------------------------------
# shard_map plumbing (kept here so callers never touch PartitionSpecs)
# ---------------------------------------------------------------------------


def dp_shard_map(per_shard, mesh, axis: str):
    """Wrap the compressed-DP per-shard step: params replicated in,
    (residual, batch) sharded over ``axis``; (loss, metrics, grads)
    replicated out, residual sharded back.  Replication checking is off:
    the compressed psum returns replicated outputs by construction."""
    return jax.shard_map(
        per_shard,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=(P(), P(), P(), P(axis)),
        check_vma=False,
    )
