"""Plain reference of a dense decoder-only transformer, in float32.

Straight ``jax.numpy`` at ``highest`` matmul precision: no kernels, no
cache, no batching, no import of the program.  Weights are regenerated
layer by layer from the seed (``bench.weights``), so nothing the program
holds is read.  The layer equations are the published ones with the
departures each configuration file lists (RMSNorm with a gain, RoPE on
interleaved pairs, an embedding scaled by sqrt(d_model), attention
scaled by 1/sqrt(head_dim), no Granite multipliers).

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


def dims_of(model: dict) -> dict:
    """Matrix shapes (K, N) by role, from a configuration's model block."""
    d, hd = model["d_model"], model["head_dim"]
    H, KV, F = model["n_heads"], model["n_kv_heads"], model["d_ff"]
    mats = {"wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd), "wo": (H * hd, d),
            "w_up": (d, F), "w_down": (F, d)}
    if model["mlp"] == "swiglu":
        mats["w_gate"] = (d, F)
    dims = dict(mats, matrices=tuple(mats), n_layers=model["n_layers"], d_model=d)
    if not model["tied_embedding"]:
        dims["lm_head"] = (d, model["vocab_rows"])
    return dims


@functools.partial(jax.jit, static_argnames=("model", "n_bits", "precision"))
def _layer(seed, layer, x, *, model, n_bits: int, precision: str):
    m = dict(model)
    dims = dims_of(m)
    p = weights.layer_leaves(seed, layer, dims, n_bits)
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
            seed, "lm_head", -1, d, m["vocab_rows"], n_bits, m["n_layers"])
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
