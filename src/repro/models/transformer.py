"""Decoder-only LM assembled from a per-layer kind pattern.

The depth is organised as ``n_superblocks`` repetitions of
``cfg.layer_pattern`` (scanned, params stacked on a leading axis) plus an
unrolled tail for depths that don't divide the pattern (e.g.
recurrentgemma's 38 = 12x(rglru,rglru,local) + 2).  Layer kinds:
"attn", "local", "ssm", "rglru", each optionally "+cross" (VLM image
cross-attention sublayer).

Inputs are a batch dict: ``tokens (B,S) int32`` or ``embeds (B,S,D)``
(modality-frontend stub), optional ``cross_embeds (B,T,D)``, and for
training ``labels (B,S)``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn_mod
from . import moe as moe_mod
from . import rglru as rglru_mod
from . import ssm as ssm_mod
from .common import (
    cross_entropy,
    dense_init,
    embed_apply,
    embed_init,
    logits_apply,
    mlp_apply,
    mlp_init,
    rmsnorm,
    rmsnorm_init,
)

Params = Dict[str, Any]


def _base_kind(kind: str) -> str:
    return kind.split("+")[0]


def _has_cross(kind: str) -> bool:
    return "+cross" in kind


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_layer(key, cfg: ModelConfig, kind: str) -> Params:
    base = _base_kind(kind)
    keys = jax.random.split(key, 6)
    p: Params = {"norm1": rmsnorm_init(cfg.d_model)}
    hd = cfg.resolved_head_dim
    if base in ("attn", "local"):
        p["mixer"] = attn_mod.attn_init(keys[0], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd)
    elif base == "ssm":
        p["mixer"] = ssm_mod.ssm_init(
            keys[0], cfg.d_model, cfg.ssm_expand, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv
        )
    elif base == "rglru":
        p["mixer"] = rglru_mod.rglru_init(keys[0], cfg.d_model, cfg.d_model)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    if _has_cross(kind):
        p["norm_cross"] = rmsnorm_init(cfg.d_model)
        p["cross"] = attn_mod.attn_init(keys[1], cfg.d_model, cfg.n_heads, cfg.n_kv_heads, hd)
    if cfg.d_ff > 0:
        p["norm2"] = rmsnorm_init(cfg.d_model)
        if cfg.n_experts > 0:
            p["moe"] = moe_mod.moe_init(
                keys[2], cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.n_shared_experts, cfg.mlp_type
            )
        else:
            p["mlp"] = mlp_init(keys[2], cfg.d_model, cfg.d_ff, cfg.mlp_type)
    return p


def _init_superblock(key, cfg: ModelConfig) -> Params:
    keys = jax.random.split(key, len(cfg.layer_pattern))
    return {f"p{i}": _init_layer(keys[i], cfg, k) for i, k in enumerate(cfg.layer_pattern)}


def init_params(key, cfg: ModelConfig) -> Params:
    k_emb, k_blocks, k_tail, k_head = jax.random.split(key, 4)
    params: Params = {}
    params["embed"] = embed_init(k_emb, cfg.padded_vocab, cfg.d_model)
    nb = cfg.n_superblocks
    params["blocks"] = jax.vmap(lambda k: _init_superblock(k, cfg))(jax.random.split(k_blocks, nb))
    if cfg.n_tail_layers:
        tkeys = jax.random.split(k_tail, cfg.n_tail_layers)
        params["tail"] = [
            _init_layer(tkeys[i], cfg, cfg.layer_pattern[i]) for i in range(cfg.n_tail_layers)
        ]
    params["final_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, cfg.d_model, cfg.padded_vocab, scale=0.02)
    return params


def init_packed_params(key, cfg: ModelConfig, n_bits: int) -> Params:
    """``core.packing.pack_model_params(init_params(key, cfg), n_bits)``
    without ever holding the float model: each superblock (and tail
    layer) is drawn and packed by one jitted program, then the packed
    slices are stacked.  Peak device memory is about twice the packed
    tree plus one float superblock, so a 2.5 B-parameter model packs on
    one 16 GB chip, where its float32 init alone takes 10 GB."""
    from ..core.packing import PackedWeight, pack_model_params

    k_emb, k_blocks, k_tail, k_head = jax.random.split(key, 4)
    # Wrapped under the same top-level names as init_params so
    # pack_model_params makes the same per-leaf packing decisions.
    pack_sb = jax.jit(lambda k: pack_model_params(
        {"blocks": _init_superblock(k, cfg)}, n_bits)["blocks"])
    nb = cfg.n_superblocks
    parts = [pack_sb(k) for k in jax.random.split(k_blocks, nb)]

    def stack(*leaves):
        if isinstance(leaves[0], PackedWeight):  # per-slice scale -> lead + (1, 1)
            out = jax.tree.map(lambda *xs: jnp.stack(xs), *leaves)
            return dataclasses.replace(out, scale=out.scale.reshape(nb, 1, 1))
        return jnp.stack(leaves)

    is_pw = lambda x: isinstance(x, PackedWeight)
    params: Params = {"embed": embed_init(k_emb, cfg.padded_vocab, cfg.d_model)}
    params["blocks"] = jax.tree.map(stack, *parts, is_leaf=is_pw)
    del parts
    if cfg.n_tail_layers:
        tkeys = jax.random.split(k_tail, cfg.n_tail_layers)
        params["tail"] = [
            jax.jit(lambda k, kind=cfg.layer_pattern[i]: pack_model_params(
                {"tail": [_init_layer(k, cfg, kind)]}, n_bits)["tail"][0])(tkeys[i])
            for i in range(cfg.n_tail_layers)
        ]
    params["final_norm"] = rmsnorm_init(cfg.d_model)
    if not cfg.tie_embeddings:
        params["lm_head"] = pack_model_params(
            {"lm_head": dense_init(k_head, cfg.d_model, cfg.padded_vocab, scale=0.02)},
            n_bits)["lm_head"]
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _apply_layer_fwd(
    p: Params, x: jax.Array, cfg: ModelConfig, kind: str, cross_src: Optional[jax.Array]
) -> Tuple[jax.Array, jax.Array, Any]:
    """Returns (x, aux_loss, cache_seed) for one layer."""
    base = _base_kind(kind)
    hd = cfg.resolved_head_dim
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if base in ("attn", "local"):
        win = cfg.window if base == "local" else None
        out, (k, v) = attn_mod.attention(
            p["mixer"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
            rope_theta=cfg.rope_theta, window=win, unroll=not cfg.scan_layers,
            scores_dtype=jnp.dtype(cfg.attn_scores_dtype),
        )
        seed = {"k": k, "v": v}
    elif base == "ssm":
        out, hT = ssm_mod.ssm_apply(
            p["mixer"], h, expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim,
            state=cfg.ssm_state, chunk=cfg.ssm_chunk,
        )
        W = cfg.ssm_conv
        # conv tail recomputed cheaply at the prefill->decode handoff
        seed = {"state": hT, "conv_tail_src": h[:, -(W - 1):, :] if h.shape[1] >= W - 1 else h}
    elif base == "rglru":
        out, (hT, conv_tail) = rglru_mod.rglru_apply(p["mixer"], h)
        seed = {"state": hT, "conv_tail": conv_tail}
    else:
        raise ValueError(kind)
    x = x + out
    if _has_cross(kind) and cross_src is not None:
        hc = rmsnorm(p["norm_cross"], x, cfg.norm_eps)
        x = x + attn_mod.cross_attention(
            p["cross"], hc, cross_src, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd
        )
    if cfg.d_ff > 0:
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if cfg.n_experts > 0:
            y, aux = moe_mod.moe_apply(
                p["moe"], h2, top_k=cfg.top_k, n_experts=cfg.n_experts,
                capacity_factor=cfg.capacity_factor, mlp_kind=cfg.mlp_type,
                n_shared=cfg.n_shared_experts,
            )
        else:
            y = mlp_apply(p["mlp"], h2, cfg.mlp_type, cfg.act_bits)
        x = x + y
    return x, aux, seed


def _superblock_fwd(x, blk: Params, cfg: ModelConfig, cross_src):
    aux_total = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(cfg.layer_pattern):
        x, aux, _ = _apply_layer_fwd(blk[f"p{i}"], x, cfg, kind, cross_src)
        aux_total = aux_total + aux
    return x, aux_total


def forward(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    """Full-sequence forward. Returns (logits (B,S,V), aux_loss)."""
    dt = cfg.compute_dtype
    if "embeds" in batch and batch["embeds"] is not None:
        x = batch["embeds"].astype(dt)
    else:
        x = embed_apply(params["embed"], batch["tokens"], dt)
        x = x * jnp.asarray(cfg.d_model**0.5, dt)
    cross_src = batch.get("cross_embeds")
    if cross_src is not None:
        cross_src = cross_src.astype(dt)

    body = functools.partial(_superblock_fwd, cfg=cfg, cross_src=cross_src)
    if cfg.remat and cfg.remat_policy != "none":
        policy = {
            "nothing": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            # save only the wide MLP activations (the dominant recompute)
            "mlp_names": jax.checkpoint_policies.save_only_these_names("mlp_wide"),
            # save matmul outputs but stream them to host DRAM: HBM
            # residency of the saved set goes to ~zero, recompute still
            # avoided (costs PCIe bandwidth on real hardware)
            "dots_offload": jax.checkpoint_policies.offload_dot_with_no_batch_dims(
                "device", "pinned_host"),
        }[cfg.remat_policy]
        body = jax.checkpoint(body, policy=policy)

    if cfg.scan_layers:
        def scan_body(carry, blk):
            x, aux = carry
            x, aux_i = body(x, blk)
            return (x, aux + aux_i), None

        (x, aux), _ = jax.lax.scan(scan_body, (x, jnp.zeros((), jnp.float32)), params["blocks"])
    else:  # unrolled (dry-run accounting: see ModelConfig.scan_layers)
        aux = jnp.zeros((), jnp.float32)
        for b in range(cfg.n_superblocks):
            blk = jax.tree.map(lambda a: a[b], params["blocks"])
            x, aux_i = body(x, blk)
            aux = aux + aux_i
    for i in range(cfg.n_tail_layers):
        x, aux_i, _ = _apply_layer_fwd(
            params["tail"][i], x, cfg, cfg.layer_pattern[i], cross_src
        )
        aux = aux + aux_i
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = logits_apply(head, x, cfg.logit_softcap)
    return logits, aux


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig):
    logits, aux = forward(params, batch, cfg)
    ce = cross_entropy(logits, batch["labels"], cfg.padded_vocab)
    return ce + cfg.router_aux_weight * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# KV / recurrent caches + decode
# ---------------------------------------------------------------------------


def _init_layer_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int, dtype,
                      paged_blocks: Optional[int] = None,
                      block_size: Optional[int] = None):
    base = _base_kind(kind)
    hd = cfg.resolved_head_dim
    if base == "attn":
        if paged_blocks is not None:
            # paged layout: a global pool of fixed-size blocks shared by
            # every lane; the per-lane block table (owned by the slot
            # pool) maps logical rows onto it.  Head-major blocks so a
            # (block_size, hd) tile per KV head is one Pallas block.
            shape = (paged_blocks, cfg.n_kv_heads, block_size, hd)
        else:
            shape = (batch, max_len, cfg.n_kv_heads, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if base == "local":
        wc = min(cfg.window, max_len)
        shape = (batch, wc, cfg.n_kv_heads, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if base == "ssm":
        d_inner, H, conv_dim = ssm_mod.ssm_dims(
            cfg.d_model, cfg.ssm_expand, cfg.ssm_head_dim, cfg.ssm_state
        )
        return {
            "state": jnp.zeros((batch, H, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
            "conv": jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
        }
    if base == "rglru":
        return {
            "state": jnp.zeros((batch, cfg.d_model), jnp.float32),
            "conv": jnp.zeros((batch, 3, cfg.d_model), dtype),
        }
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               paged_blocks: Optional[int] = None,
               block_size: Optional[int] = None):
    """Decode cache for ``batch`` lanes.  With ``paged_blocks`` /
    ``block_size``, full-length attention K/V leaves become a shared pool
    of ``paged_blocks`` fixed-size blocks instead of per-lane ``max_len``
    reservations (ring buffers and recurrent state keep their fixed
    per-lane shapes — they are already bounded, so they bypass paging)."""
    per_block = {
        f"p{i}": _init_layer_cache(cfg, k, batch, max_len, dtype, paged_blocks,
                                   block_size)
        for i, k in enumerate(cfg.layer_pattern)
    }
    blocks = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_superblocks,) + a.shape), per_block
    )
    cache = {"blocks": blocks}
    if cfg.n_tail_layers:
        cache["tail"] = [
            _init_layer_cache(cfg, cfg.layer_pattern[i], batch, max_len, dtype,
                              paged_blocks, block_size)
            for i in range(cfg.n_tail_layers)
        ]
    return cache


def _apply_layer_decode(p, x, cfg: ModelConfig, kind: str, cache, pos, cross_src,
                        active=None, block_table=None, paged_kernel=False):
    base = _base_kind(kind)
    hd = cfg.resolved_head_dim
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    new_cache = dict(cache)
    if base in ("attn", "local"):
        ring = base == "local"
        out, nk, nv = attn_mod.decode_attention_cache(
            p["mixer"], h, cache["k"], cache["v"], pos,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
            rope_theta=cfg.rope_theta,
            window=cfg.window if base == "local" else None, ring=ring,
            active=active, block_table=block_table, paged_kernel=paged_kernel,
        )
        new_cache = {"k": nk, "v": nv}
    elif base == "ssm":
        out, hT, conv = ssm_mod.ssm_decode(
            p["mixer"], h, cache["state"], cache["conv"],
            expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
        )
        if active is not None:
            # inactive/prefilling lanes must not integrate garbage: the
            # recurrent state would drift unboundedly on long-idle lanes
            # and clobber a mid-prefill lane's carried state.
            hT = jnp.where(active[:, None, None, None], hT, cache["state"])
            conv = jnp.where(active[:, None, None], conv, cache["conv"])
        new_cache = {"state": hT, "conv": conv}
    elif base == "rglru":
        out, hT, conv = rglru_mod.rglru_decode(p["mixer"], h, cache["state"], cache["conv"])
        if active is not None:
            hT = jnp.where(active[:, None], hT, cache["state"])
            conv = jnp.where(active[:, None, None], conv, cache["conv"])
        new_cache = {"state": hT, "conv": conv}
    x = x + out
    if _has_cross(kind) and cross_src is not None:
        hc = rmsnorm(p["norm_cross"], x, cfg.norm_eps)
        x = x + attn_mod.cross_attention(
            p["cross"], hc, cross_src, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd
        )
    if cfg.d_ff > 0:
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if cfg.n_experts > 0:
            y, _ = moe_mod.moe_apply(
                p["moe"], h2, top_k=cfg.top_k, n_experts=cfg.n_experts,
                capacity_factor=cfg.capacity_factor, mlp_kind=cfg.mlp_type,
                n_shared=cfg.n_shared_experts,
            )
        else:
            y = mlp_apply(p["mlp"], h2, cfg.mlp_type, cfg.act_bits)
        x = x + y
    return x, new_cache


def decode_step(
    params: Params,
    cache,
    tokens: jax.Array,  # (B, 1) int32 or embeds (B, 1, D)
    pos: jax.Array,  # scalar int32, or (B,) int32 per-slot positions
    cfg: ModelConfig,
    cross_embeds: Optional[jax.Array] = None,
    active: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
    paged_kernel: bool = False,
):
    """One decode step for the whole model. Returns (logits (B,V), cache).

    ``pos`` is either a scalar (all lanes at the same depth — the
    bucketed serving path) or a (B,) vector of per-slot positions (the
    continuous-batching slot pool: each lane is an independent request;
    attention layers apply per-lane RoPE/causal masking, recurrent layers
    are position-free so the vector passes through untouched).

    ``active`` (per-slot pools only): (B,) bool marking the lanes that
    are actually decoding.  Inactive lanes still flow through the whole
    computation — that is what keeps this ONE compiled program — but
    their persistent state (attention cache row, recurrent state/conv)
    is held fixed instead of absorbing garbage: free lanes stay finite
    under long idle, and lanes mid-way through a chunked prefill keep
    the prompt state the interleaved decode step would otherwise
    clobber.

    ``block_table`` ((B, blocks_per_lane) int32, per-slot pools only)
    selects the PAGED cache layout for full-length attention layers: the
    cache's ``k``/``v`` leaves are a shared block pool and each lane's
    reads/writes route through its table row (see
    ``attention.decode_attention``).  Ring/ssm/rglru state is fixed-size
    per lane and bypasses paging.  ``paged_kernel=True`` makes those
    paged reads walk the table block-by-block via the Pallas kernel
    instead of gathering the full pool view."""
    dt = cfg.compute_dtype
    if tokens.ndim == 3:
        x = tokens.astype(dt)
    else:
        x = embed_apply(params["embed"], tokens, dt) * jnp.asarray(cfg.d_model**0.5, dt)
    cross_src = None if cross_embeds is None else cross_embeds.astype(dt)

    def scan_body(x, inp):
        blk, blk_cache = inp
        new_cache = {}
        for i, kind in enumerate(cfg.layer_pattern):
            x, new_cache[f"p{i}"] = _apply_layer_decode(
                blk[f"p{i}"], x, cfg, kind, blk_cache[f"p{i}"], pos, cross_src,
                active, block_table, paged_kernel
            )
        return x, new_cache

    if cfg.scan_layers:
        x, new_blocks = jax.lax.scan(scan_body, x, (params["blocks"], cache["blocks"]))
    else:
        outs = []
        for b in range(cfg.n_superblocks):
            blk = jax.tree.map(lambda a: a[b], params["blocks"])
            blk_cache = jax.tree.map(lambda a: a[b], cache["blocks"])
            x, nc = scan_body(x, (blk, blk_cache))
            outs.append(nc)
        new_blocks = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
    new_cache = {"blocks": new_blocks}
    if cfg.n_tail_layers:
        new_tail = []
        for i in range(cfg.n_tail_layers):
            x, c = _apply_layer_decode(
                params["tail"][i], x, cfg, cfg.layer_pattern[i], cache["tail"][i],
                pos, cross_src, active, block_table, paged_kernel
            )
            new_tail.append(c)
        new_cache["tail"] = new_tail
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = logits_apply(head, x, cfg.logit_softcap)
    return logits[:, 0], new_cache


# ---------------------------------------------------------------------------
# Prefill: run forward and seed the decode cache
# ---------------------------------------------------------------------------


def prefill(params: Params, batch: Dict[str, jax.Array], cfg: ModelConfig, max_len: int,
            cache_dtype=jnp.bfloat16):
    """Full-sequence prefill that also populates a decode cache.

    Returns (last_token_logits, cache, seq_len).  Implemented by running
    the layer-level forward unscanned per superblock (cache seeds need to
    escape the scan), so it's used for serving, not the train step.
    """
    dt = cfg.compute_dtype
    if "embeds" in batch and batch["embeds"] is not None:
        x = batch["embeds"].astype(dt)
        B, S = x.shape[0], x.shape[1]
    else:
        B, S = batch["tokens"].shape
        x = embed_apply(params["embed"], batch["tokens"], dt) * jnp.asarray(cfg.d_model**0.5, dt)
    cross_src = batch.get("cross_embeds")
    if cross_src is not None:
        cross_src = cross_src.astype(dt)
    cache = init_cache(cfg, B, max_len, cache_dtype)

    # Unrolled over superblocks (prefill compiles once per shape; the
    # unroll is acceptable for the serving path and keeps seeds reachable).
    blocks = params["blocks"]
    new_blocks = []
    aux = jnp.zeros((), jnp.float32)
    for b in range(cfg.n_superblocks):
        blk = jax.tree.map(lambda a: a[b], blocks)
        blk_cache = jax.tree.map(lambda a: a[b], cache["blocks"])
        ncache = {}
        for i, kind in enumerate(cfg.layer_pattern):
            x, aux_i, seed = _apply_layer_fwd(blk[f"p{i}"], x, cfg, kind, cross_src)
            aux = aux + aux_i
            ncache[f"p{i}"] = _seed_layer_cache(
                blk[f"p{i}"], cfg, kind, seed, blk_cache[f"p{i}"], S, cache_dtype
            )
        new_blocks.append(ncache)
    cache["blocks"] = jax.tree.map(lambda *xs: jnp.stack(xs), *new_blocks)
    if cfg.n_tail_layers:
        new_tail = []
        for i in range(cfg.n_tail_layers):
            x, aux_i, seed = _apply_layer_fwd(params["tail"][i], x, cfg,
                                              cfg.layer_pattern[i], cross_src)
            new_tail.append(
                _seed_layer_cache(params["tail"][i], cfg, cfg.layer_pattern[i],
                                  seed, cache["tail"][i], S, cache_dtype)
            )
        cache["tail"] = new_tail
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = logits_apply(head, x[:, -1:], cfg.logit_softcap)
    return logits[:, 0], cache


def _seed_layer_cache(layer_params, cfg: ModelConfig, kind, seed, layer_cache, S, cache_dtype):
    base = _base_kind(kind)
    if base == "attn":
        k, v = seed["k"].astype(cache_dtype), seed["v"].astype(cache_dtype)
        return {
            "k": jax.lax.dynamic_update_slice_in_dim(layer_cache["k"], k, 0, axis=1),
            "v": jax.lax.dynamic_update_slice_in_dim(layer_cache["v"], v, 0, axis=1),
        }
    if base == "local":
        wc = layer_cache["k"].shape[1]
        k, v = seed["k"], seed["v"]
        take = min(wc, S)
        pos = jnp.arange(S - take, S)
        slots = pos % wc
        return {
            "k": layer_cache["k"].at[:, slots].set(k[:, S - take:].astype(cache_dtype)),
            "v": layer_cache["v"].at[:, slots].set(v[:, S - take:].astype(cache_dtype)),
        }
    if base == "ssm":
        # state carried exactly; conv state = last W-1 post-norm inputs'
        # xBC projection (recomputed here — cheap: (W-1) tokens).
        W = cfg.ssm_conv
        h_tail = seed["conv_tail_src"]
        p = layer_params["mixer"]
        d_inner, H, conv_dim = ssm_mod.ssm_dims(
            cfg.d_model, cfg.ssm_expand, cfg.ssm_head_dim, cfg.ssm_state
        )
        proj = h_tail @ p["in_proj"].astype(h_tail.dtype)
        xBC = proj[..., d_inner : d_inner + conv_dim]
        pad = (W - 1) - xBC.shape[1]
        if pad > 0:
            xBC = jnp.pad(xBC, ((0, 0), (pad, 0), (0, 0)))
        return {"state": seed["state"], "conv": xBC.astype(cache_dtype)}
    if base == "rglru":
        conv = seed["conv_tail"]
        pad = 3 - conv.shape[1]
        if pad > 0:
            conv = jnp.pad(conv, ((0, 0), (pad, 0), (0, 0)))
        return {"state": seed["state"], "conv": conv.astype(cache_dtype)}
    return layer_cache


# ---------------------------------------------------------------------------
# Chunked prefill: prompts stream through the pooled decode cache
# ---------------------------------------------------------------------------


def _apply_layer_prefill_chunk(p, x, cfg: ModelConfig, kind: str, cache, start,
                               n_valid, cross_src, cache_dtype, block_table=None):
    base = _base_kind(kind)
    hd = cfg.resolved_head_dim
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    if base in ("attn", "local"):
        ring = base == "local"
        out, nk, nv = attn_mod.prefill_chunk_attention(
            p["mixer"], h, cache["k"], cache["v"], start, n_valid,
            n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd,
            rope_theta=cfg.rope_theta,
            window=cfg.window if base == "local" else None,
            ring=ring,
            scores_dtype=jnp.dtype(cfg.attn_scores_dtype),
            block_table=None if ring else block_table,
        )
        new_cache = {"k": nk, "v": nv}
    elif base == "ssm":
        out, hT, conv = ssm_mod.ssm_prefill_chunk(
            p["mixer"], h, cache["state"], cache["conv"], n_valid,
            expand=cfg.ssm_expand, head_dim=cfg.ssm_head_dim, state=cfg.ssm_state,
        )
        new_cache = {"state": hT, "conv": conv.astype(cache_dtype)}
    elif base == "rglru":
        out, hT, conv = rglru_mod.rglru_prefill_chunk(
            p["mixer"], h, cache["state"], cache["conv"], n_valid
        )
        new_cache = {"state": hT, "conv": conv.astype(cache_dtype)}
    else:
        raise ValueError(kind)
    x = x + out
    if _has_cross(kind) and cross_src is not None:
        hc = rmsnorm(p["norm_cross"], x, cfg.norm_eps)
        x = x + attn_mod.cross_attention(
            p["cross"], hc, cross_src, n_heads=cfg.n_heads, n_kv=cfg.n_kv_heads, head_dim=hd
        )
    if cfg.d_ff > 0:
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if cfg.n_experts > 0:
            y, _ = moe_mod.moe_apply(
                p["moe"], h2, top_k=cfg.top_k, n_experts=cfg.n_experts,
                capacity_factor=cfg.capacity_factor, mlp_kind=cfg.mlp_type,
                n_shared=cfg.n_shared_experts,
            )
        else:
            y = mlp_apply(p["mlp"], h2, cfg.mlp_type, cfg.act_bits)
        x = x + y
    return x, new_cache


def prefill_chunk(
    params: Params,
    cache,
    tokens: jax.Array,  # (B, C) int32 — one fixed-size chunk per lane
    start: jax.Array,  # (B,) int32 — the chunk's first absolute position
    n_valid: jax.Array,  # (B,) int32 — real tokens in this chunk (rest pad)
    cfg: ModelConfig,
    cache_dtype=jnp.bfloat16,
    cross_embeds: Optional[jax.Array] = None,
    block_table: Optional[jax.Array] = None,
    return_all_logits: bool = False,
):
    """One fixed-size prefill chunk over the whole slot pool.

    The chunked-prefill counterpart of :func:`prefill`: instead of a
    batch-1 full-prompt forward compiled per prompt length, each call
    consumes up to C prompt tokens *per lane* and writes the results
    straight into the pooled decode cache — attention K/V land in the
    lane's rows [start, start+n_valid), recurrent layers advance their
    carried state.  The compiled-program set is therefore O(#chunk
    sizes), independent of the workload's prompt-length distribution.

    Lanes that are not prefilling ride along as no-ops (``n_valid = 0``,
    ``start = max_len``): their compute is garbage but their cache is
    provably untouched — that is what lets the scheduler interleave
    prefill chunks with pooled decode steps without forking programs.

    ``block_table`` routes full-length attention K/V through the paged
    block pool (see :func:`decode_step`); the scheduler must have
    allocated each prefilling lane's blocks for rows
    [start, start + n_valid) before dispatch.

    Returns (last_logits (B, V), new_cache): ``last_logits[b]`` is the
    logits at lane b's last real token of this chunk — the scheduler
    samples the first generated token from it when the chunk completes
    the lane's prompt (rows of lanes that didn't finish are garbage and
    must be ignored).

    ``return_all_logits=True`` returns ``(logits (B, C, V), new_cache)``
    instead — the logits at EVERY chunk position (positions >= n_valid
    are garbage).  This is the spec-decode verify step: one chunk pass
    at full precision scores every drafted position at once."""
    dt = cfg.compute_dtype
    x = embed_apply(params["embed"], tokens, dt) * jnp.asarray(cfg.d_model**0.5, dt)
    cross_src = None if cross_embeds is None else cross_embeds.astype(dt)

    new_blocks = []
    for b in range(cfg.n_superblocks):
        blk = jax.tree.map(lambda a: a[b], params["blocks"])
        blk_cache = jax.tree.map(lambda a: a[b], cache["blocks"])
        ncache = {}
        for i, kind in enumerate(cfg.layer_pattern):
            x, ncache[f"p{i}"] = _apply_layer_prefill_chunk(
                blk[f"p{i}"], x, cfg, kind, blk_cache[f"p{i}"], start, n_valid,
                cross_src, cache_dtype, block_table,
            )
        new_blocks.append(ncache)
    new_cache = {"blocks": jax.tree.map(lambda *xs: jnp.stack(xs), *new_blocks)}
    if cfg.n_tail_layers:
        new_tail = []
        for i in range(cfg.n_tail_layers):
            x, c = _apply_layer_prefill_chunk(
                params["tail"][i], x, cfg, cfg.layer_pattern[i], cache["tail"][i],
                start, n_valid, cross_src, cache_dtype, block_table,
            )
            new_tail.append(c)
        new_cache["tail"] = new_tail
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    if return_all_logits:
        return logits_apply(head, x, cfg.logit_softcap), new_cache
    # logits only at each lane's last real token (same row math as
    # prefill's x[:, -1:], so greedy stays token-identical to the oracle)
    last = jnp.clip(n_valid - 1, 0, tokens.shape[1] - 1)
    x_last = jnp.take_along_axis(x, last[:, None, None], axis=1)
    logits = logits_apply(head, x_last, cfg.logit_softcap)
    return logits[:, 0], new_cache
