"""Shared model components: norms, RoPE, MLPs, embeddings, initialisers.

Pure-functional: params are nested dicts of arrays; every `apply` is a
free function.  Weight tensors use  (in, out)  layout so a quantised
PackedWeight (K, N) maps 1:1.
"""
from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Dict

import jax
import jax.numpy as jnp

from ..core.ste import relu6_act_quantize

Params = Dict[str, jax.Array]

# Mesh over which packed matmuls are shard_map'd (None = GSPMD-managed).
# Set for the duration of a trace by the serve engine via
# packed_shard_mesh(); read by dense_apply at trace time.  A ContextVar,
# not a module global: concurrent traces (e.g. a sharded engine and a
# single-device reference engine in one process) must not see each
# other's mesh.
_packed_mesh_var: contextvars.ContextVar = contextvars.ContextVar(
    "packed_shard_mesh", default=None
)


@contextlib.contextmanager
def packed_shard_mesh(mesh):
    """Trace the enclosed computation with packed matmuls shard_map'd.

    Inside this context, dense_apply routes annotated PackedWeights
    (``kn_spec`` set by ``dist.sharding.annotate_packed_specs``) through
    ``kernels.ops.bitserial_matmul_sharded``: each shard runs the
    bitserial kernel on its local packed bytes and a psum stitches the
    contraction — required on TPU because the Pallas kernel is a custom
    call GSPMD cannot partition.  ``mesh=None`` is a no-op (unsharded /
    single-device serving)."""
    token = _packed_mesh_var.set(mesh)
    try:
        yield
    finally:
        _packed_mesh_var.reset(token)


# Mesh over which paged decode attention is shard_map'd (None = GSPMD).
# Same ContextVar discipline as _packed_mesh_var: set by the scheduler
# for the duration of the decode trace when the block tables are
# data-sharded (dist.sharding.table_shards > 1), read by
# models.attention.decode_attention.
_paged_mesh_var: contextvars.ContextVar = contextvars.ContextVar(
    "paged_shard_mesh", default=None
)


@contextlib.contextmanager
def paged_shard_mesh(mesh):
    """Trace the enclosed computation with paged decode attention
    shard_map'd over ``mesh``: each data shard scatters/gathers only its
    local slice of the KV block pool (lanes and their blocks co-shard,
    see ``dist.sharding.block_table_spec``), so the pool is never
    all-gathered — GSPMD would do exactly that at the opaque Pallas
    paged-attention call.  ``mesh=None`` is a no-op."""
    token = _paged_mesh_var.set(mesh)
    try:
        yield
    finally:
        _paged_mesh_var.reset(token)


def paged_mesh():
    """The mesh set by :func:`paged_shard_mesh` for the current trace."""
    return _paged_mesh_var.get()


# Runtime active-plane count for packed matmuls (None = all planes).
# Set for the duration of a trace by the spec-decode draft dispatch via
# active_plane_count(); read by dense_apply at trace time.  The value is
# typically a TRACED int32 scalar (a jitted program operand), which is
# the whole point: one compiled decode program serves every precision
# level — draft steps pass draft_planes, verify passes n_bits — with no
# recompilation.  Same ContextVar discipline as _packed_mesh_var.
_active_planes_var: contextvars.ContextVar = contextvars.ContextVar(
    "active_plane_count", default=None
)


@contextlib.contextmanager
def active_plane_count(n):
    """Trace the enclosed computation with packed matmuls restricted to
    the ``n`` most significant bit planes at RUNTIME (bitwise-equal to
    statically truncating via ``core.packing.truncate_packed``; see
    ``kernels.ops.bitserial_matmul``).  ``n=None`` is a no-op (full
    precision)."""
    token = _active_planes_var.set(n)
    try:
        yield
    finally:
        _active_planes_var.reset(token)


def dense_apply(x: jax.Array, w) -> jax.Array:
    """x @ w, dispatching on representation: plain array, or a BSQ
    PackedWeight (sign+magnitude bit-planes) dequantised on the fly —
    HBM weight traffic becomes (n_bits+1)/16 of bf16 (§Perf serving).
    Under packed_shard_mesh(), annotated PackedWeights run per-shard
    (shard_map + psum) instead of relying on GSPMD."""
    from ..core.packing import PackedWeight
    from ..kernels import ops

    if isinstance(w, PackedWeight):
        mesh = _packed_mesh_var.get()
        active = _active_planes_var.get()
        if (
            mesh is not None
            and w.kn_spec is not None
            and any(a is not None for a in w.kn_spec)
        ):
            return ops.bitserial_matmul_sharded(x, w, mesh, active_planes=active)
        # use_pallas=None -> ops dispatches by backend (Pallas kernel on
        # TPU, fused-unpack XLA ref elsewhere).
        return ops.bitserial_matmul(x, w, active_planes=active, use_pallas=None)
    return x @ w.astype(x.dtype)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def dense_init(key, d_in: int, d_out: int, scale: float | None = None, dtype=jnp.float32):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return jax.random.normal(key, (d_in, d_out), dtype) * scale


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return jax.random.normal(key, (vocab, d), dtype) * 0.02


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int):
    return {"scale": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + p["scale"].astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, hd); positions: broadcastable to (..., S).

    Rotation pairs are INTERLEAVED (2j, 2j+1), not half-split (j, j+hd/2):
    the pair then lives in a (hd/2, 2) minor axis after a shard-aligned
    reshape, so the op stays elementwise-local when hd derives from a
    model-sharded projection.  The half-split form slices/concats across
    the sharded axis, which XLA's CPU SPMD partitioner handles via
    "involuntary full rematerialization" — and miscompiles (wrong values,
    observed with hd sharded and batch replicated).  Both
    conventions are valid RoPE; all call sites (train/prefill/decode)
    share this one, so caches stay consistent.
    """
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    sin = jnp.sin(ang)[..., None, :]  # (..., S, 1, hd/2)
    cos = jnp.cos(ang)[..., None, :]
    xr = x.astype(jnp.float32).reshape(*x.shape[:-1], hd // 2, 2)
    a, b = xr[..., 0], xr[..., 1]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_init(key, d: int, d_ff: int, kind: str) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {
            "w_gate": dense_init(k1, d, d_ff),
            "w_up": dense_init(k2, d, d_ff),
            "w_down": dense_init(k3, d_ff, d),
        }
    return {"w_up": dense_init(k1, d, d_ff), "w_down": dense_init(k2, d_ff, d)}


def mlp_apply(p: Params, x: jax.Array, kind: str, act_bits: int = 32) -> jax.Array:
    from jax.ad_checkpoint import checkpoint_name

    dt = x.dtype
    if kind in ("swiglu", "geglu"):
        g = checkpoint_name(dense_apply(x, p["w_gate"]), "mlp_wide")
        u = checkpoint_name(dense_apply(x, p["w_up"]), "mlp_wide")
        h = (jax.nn.silu(g) if kind == "swiglu" else jax.nn.gelu(g, approximate=True)) * u
    elif kind == "gelu_mlp":
        h = jax.nn.gelu(checkpoint_name(dense_apply(x, p["w_up"]), "mlp_wide"),
                        approximate=True)
    else:
        h = jax.nn.relu(checkpoint_name(dense_apply(x, p["w_up"]), "mlp_wide"))
    if act_bits < 32:
        h = relu6_act_quantize(h, act_bits).astype(dt)
    return dense_apply(h, p["w_down"])


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------


def embed_apply(table: jax.Array, tokens: jax.Array, dtype) -> jax.Array:
    return table.astype(dtype)[tokens]


def logits_apply(head, x: jax.Array, softcap: float = 0.0) -> jax.Array:
    logits = dense_apply(x, head).astype(jnp.float32)
    if softcap > 0:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array, vocab: int) -> jax.Array:
    """Mean CE over tokens; labels == -1 are masked.

    SPMD note (§Perf cell-A iteration): the obvious
    ``take_along_axis(logits, labels)`` gathers across the model-sharded
    vocab axis, and its transpose (a scatter) makes GSPMD replicate the
    (B, S, V) logits cotangent over the *batch* axes — a 12 GiB f32
    all-reduce per step at train_4k scale.  The masked-select form below
    is elementwise over V, so both it and its VJP keep the batch
    sharding: per-device logits-grad stays (B/dp, S, V/tp).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    v_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    sel = v_iota == jnp.maximum(labels, 0)[..., None]
    picked = jnp.sum(jnp.where(sel, logits, 0.0), axis=-1)
    nll = lse - picked
    mask = (labels >= 0).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
