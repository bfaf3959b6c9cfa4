"""Plain reference of a dense decoder-only transformer, in float32.

Straight ``jax.numpy`` at ``highest`` matmul precision: no kernels, no
cache, no batching, no import of the program.  Weights are regenerated
layer by layer from the seed (``bench.weights``), so nothing the program
holds is read.  The layer equations are the published ones with the
departures each configuration file lists (RMSNorm with a gain, RoPE on
interleaved pairs, an embedding scaled by sqrt(d_model), attention
scaled by 1/sqrt(head_dim), no Granite multipliers).

Besides ``logits``, the module describes the architecture to the
harness, which finds it by the configuration's ``reference`` key
(``bench.check.reference``): ``dims_of`` (roles, shapes and the kind of
each layer), ``weight_std`` and ``FLOAT_LEAVES`` (how the seeded weights
are drawn), ``program_differs`` (the program's configuration against
the model block) and ``model_flops`` (for ``step_mfu``).

``precision="fp8"`` is the control: every matmul operand is rounded to
float8_e4m3fn (with one scale per tensor, as an fp8 path would keep)
before a float32 product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .. import weights

FP8_MAX = 448.0  # largest finite float8_e4m3fn
KIND = "attn"  # every layer attends and has the MLP
NORMS = ("norm1", "norm2")
# Float leaves other than norm gains, the embedding and the final norm,
# by role: ``draw(key, shape)``.  A dense layer has none.
FLOAT_LEAVES: dict = {}

# Residual branches together add this many times the embedding's
# variance to the stream.
STREAM_GROWTH = 4.0
# Score standard deviation of a query-key product (sharp enough that
# attention output depends on a few positions, not a flat mean).
SCORE_STD = 3.0
# rms of attention output and of the MLP's gated activation, for unit
# inputs (measured on random draws; only used to size output scales).
ATTN_OUT_RMS = 0.5
MLP_ACT_RMS = 0.6


def _round(x, precision: str):
    if precision == "f32":
        return x
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = FP8_MAX / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(a, b, precision: str):
    return jnp.matmul(_round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST)


def dequantise(planes, sign, scale, k: int):
    """Packed bytes -> float32 (K, N) by the packed format's definition:
    bit ``j`` of byte ``r`` along K is row ``8r + j`` (LSB first), plane
    ``b`` carries magnitude bit ``b``, the sign byte's bit is 1 for
    negative, and W = sign * q * scale / (2^n - 1)."""
    n_bits = planes.shape[0]

    def rows(byts):
        bits = (byts[:, None, :] >> jnp.arange(8, dtype=jnp.uint8)[None, :, None]) & 1
        return bits.reshape(-1, byts.shape[-1])[:k].astype(jnp.float32)

    mag = sum(rows(planes[b]) * (2.0**b) for b in range(n_bits))
    return (1.0 - 2.0 * rows(sign)) * mag * (scale / (2.0**n_bits - 1.0))


def rmsnorm(x, gain, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def rope(x, theta: float):
    """x (S, heads, d): rotate pairs (2i, 2i+1) by position * theta^(-2i/d)."""
    S, _, d = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # (S, d/2)
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape)


def weight_std(name: str, k: int, n_layers: int) -> float:
    """Standard deviation of the dequantised weight of matrix ``name``
    with reduction size ``k``: scales that keep an ``n_layers`` residual
    stream well conditioned (the stream's growth is shared out over the
    depth)."""
    branch = math.sqrt(STREAM_GROWTH / (2 * n_layers))
    if name in ("wq", "wk"):
        return math.sqrt(SCORE_STD) / math.sqrt(k)
    if name == "wo":
        return branch / (ATTN_OUT_RMS * math.sqrt(k))
    if name == "w_down":
        return branch / (MLP_ACT_RMS * math.sqrt(k))
    return 1.0 / math.sqrt(k)


def dims_of(model: dict) -> dict:
    """From a configuration's model block: matrix shapes (K, N) by role,
    ``matrices`` (the packed roles), ``kinds`` (the kind of each layer by
    depth), ``roles`` (by kind, every role a layer of that kind holds:
    packed matrices, norm gains, float leaves) and ``attn_layers`` (how
    many layers attend).  Every dense layer is of one kind."""
    d, hd = model["d_model"], model["head_dim"]
    H, KV, F = model["n_heads"], model["n_kv_heads"], model["d_ff"]
    mats = {"wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd), "wo": (H * hd, d),
            "w_up": (d, F), "w_down": (F, d)}
    if model["mlp"] == "swiglu":
        mats["w_gate"] = (d, F)
    L = model["n_layers"]
    dims = dict(mats, matrices=tuple(mats), n_layers=L, d_model=d, kinds=(KIND,) * L,
                roles={KIND: tuple(mats) + NORMS}, attn_layers=L)
    if not model["tied_embedding"]:
        dims["lm_head"] = (d, model["vocab_rows"])
    return dims


def program_differs(model: dict, cfg) -> dict:
    """``{key: (file, program)}`` where the program's configuration
    ``cfg`` (a ``repro`` ``ModelConfig``) disagrees with the model block."""
    want = {"d_model": cfg.d_model, "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff, "n_layers": cfg.n_layers,
            "vocab": cfg.vocab_size, "vocab_rows": cfg.padded_vocab,
            "tied_embedding": cfg.tie_embeddings, "rope_theta": cfg.rope_theta,
            "norm_eps": cfg.norm_eps, "mlp": cfg.mlp_type}
    differ = {k: (model[k], v) for k, v in want.items() if model[k] != v}
    if tuple(cfg.layer_pattern) != (KIND,):
        differ["layer_pattern"] = ((KIND,), tuple(cfg.layer_pattern))
    return differ


def model_flops(model: dict, dims: dict, positions) -> float:
    """Forward FLOPs for tokens at the given positions (0-based): two per
    weight of every matmul (the head over the published vocabulary) plus
    attention's q.K and p.V over the ``p + 1`` positions each token
    sees, in every layer that attends."""
    weights = (dims["n_layers"] * sum(k * n for k, n in (dims[m] for m in dims["matrices"]))
               + model["d_model"] * model["vocab"])
    attn = 4.0 * dims["attn_layers"] * model["n_heads"] * model["head_dim"]
    return sum(2.0 * weights + attn * (p + 1) for p in positions)


def layer_leaves(seed, layer, dims: dict, n_bits: int):
    """Every leaf of decoder layer ``layer``, by its published role:
    ``{name: (planes, sign, scale)}`` for the matrices and
    ``{name: gain}`` for the norms."""
    L = dims["n_layers"]
    out = {m: weights.packed_leaf(seed, m, layer, *dims[m], n_bits,
                                  weight_std(m, dims[m][0], L))
           for m in dims["matrices"]}
    for name in NORMS:
        out[name] = weights.norm_gain(seed, name, layer, dims["d_model"])
    return out


@functools.partial(jax.jit, static_argnames=("model", "n_bits", "precision"))
def _layer(seed, layer, x, *, model, n_bits: int, precision: str):
    m = dict(model)
    dims = dims_of(m)
    p = layer_leaves(seed, layer, dims, n_bits)
    w = {name: dequantise(*p[name], dims[name][0]) for name in dims["matrices"]}
    S, H, KV, hd = x.shape[0], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    h = rmsnorm(x, p["norm1"], m["norm_eps"])
    q = rope(_mm(h, w["wq"], precision).reshape(S, H, hd), m["rope_theta"])
    k = rope(_mm(h, w["wk"], precision).reshape(S, KV, hd), m["rope_theta"])
    v = _mm(h, w["wv"], precision).reshape(S, KV, hd)
    G = H // KV
    q = q.reshape(S, KV, G, hd).transpose(1, 2, 0, 3)  # (KV, G, S, hd)
    kt = k.transpose(1, 2, 0)[:, None]  # (KV, 1, hd, S)
    s = _mm(q, kt, precision) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _mm(probs, v.transpose(1, 0, 2)[:, None], precision)  # (KV, G, S, hd)
    o = o.transpose(2, 0, 1, 3).reshape(S, H * hd)
    x = x + _mm(o, w["wo"], precision)
    h = rmsnorm(x, p["norm2"], m["norm_eps"])
    if m["mlp"] == "swiglu":
        a = jax.nn.silu(_mm(h, w["w_gate"], precision)) * _mm(h, w["w_up"], precision)
    else:  # gelu_mlp: the program's GELU is the tanh form
        a = jax.nn.gelu(_mm(h, w["w_up"], precision), approximate=True)
    return x + _mm(a, w["w_down"], precision)


@functools.partial(jax.jit, static_argnames=("model",))
def _embed(seed, tokens, *, model):
    m = dict(model)
    table = weights.embedding(seed, m["vocab_rows"], m["d_model"])
    return table[tokens] * math.sqrt(m["d_model"])


@functools.partial(jax.jit, static_argnames=("model", "n_bits", "precision"))
def _head(seed, x, *, model, n_bits: int, precision: str):
    m = dict(model)
    d = m["d_model"]
    g = weights.norm_gain(seed, "final_norm", -1, d)
    x = rmsnorm(x, g, m["norm_eps"])
    if m["tied_embedding"]:
        head = weights.embedding(seed, m["vocab_rows"], d).T
    else:
        planes, sign, scale = weights.packed_leaf(
            seed, "lm_head", -1, d, m["vocab_rows"], n_bits,
            weight_std("lm_head", d, m["n_layers"]))
        head = dequantise(planes, sign, scale, d)
    return _mm(x, head, precision)[:, : m["vocab"]]


def logits(seed: int, tokens, model: dict, n_bits: int, precision: str = "f32"):
    """Logits (S, vocab) at every position of one token sequence."""
    frozen = tuple(sorted(model.items()))
    sw = weights.seed_words(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(sw, jnp.asarray(tokens, jnp.int32), model=frozen)
        for layer in range(model["n_layers"]):
            x = _layer(sw, jnp.int32(layer), x, model=frozen, n_bits=n_bits,
                       precision=precision)
        return _head(sw, x, model=frozen, n_bits=n_bits, precision=precision)
