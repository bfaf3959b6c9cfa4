"""Train-step factories: BSQ bit-representation training (Eq. 5), plain
baseline training, and the compressed-DP (shard_map) variant.

State layout (a plain dict so checkpointing/sharding see flat leaves)::

    state = {
      "trainable": {
         "reps":  {name: {"wp","wn","scale"}},   # bit-planes + scales
         "float": {name: array},                 # norms, scalars, ...
      },
      "masks":  {name: (nb, *gshape) {0,1}},     # active-plane masks (not trained)
      "opt":    optimizer state over `trainable`,
      "step":   int32,
    }

The model template (pytree structure) and BitRep static metadata
(n_denom, group_axes) are closed over — they never change during a run.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..core import bsq as bsq_mod
from ..core.bitrep import BitRep
from ..core.bsq import BSQConfig
from ..models import transformer
from ..optim.optimizers import clip_by_global_norm, project_bitplanes

PyTree = Any


@dataclasses.dataclass
class BSQTrainContext:
    cfg: ModelConfig
    bsq_cfg: BSQConfig
    template: PyTree  # pytree structure of model params (leaves unused)
    meta: Dict[str, Tuple[int, Tuple[int, ...]]]  # name -> (n_denom, group_axes)
    total_quant_params: int


def init_bsq_state(key, cfg: ModelConfig, bsq_cfg: BSQConfig, optimizer,
                   predicate=None) -> Tuple[Dict, BSQTrainContext]:
    """Initialise model params, convert to bit representation, build state."""
    params = transformer.init_params(key, cfg)
    qp, fp = bsq_mod.partition_params(params, predicate or bsq_mod.default_quant_predicate)
    reps = bsq_mod.init_bitreps(qp, bsq_cfg)
    template = jax.eval_shape(lambda: params)
    meta = {k: (r.n_denom, r.group_axes) for k, r in reps.items()}
    trainable = {
        "reps": {k: {"wp": r.wp, "wn": r.wn, "scale": r.scale} for k, r in reps.items()},
        "float": fp,
    }
    state = {
        "trainable": trainable,
        "masks": {k: r.mask for k, r in reps.items()},
        "opt": optimizer.init(trainable),
        "step": jnp.zeros((), jnp.int32),
    }
    ctx = BSQTrainContext(
        cfg=cfg, bsq_cfg=bsq_cfg, template=template, meta=meta,
        total_quant_params=bsq_mod.total_quantized_params(reps),
    )
    return state, ctx


def _reps_from_state(trainable, masks, meta) -> Dict[str, BitRep]:
    return {
        k: BitRep(
            wp=t["wp"], wn=t["wn"], scale=t["scale"],
            mask=jax.lax.stop_gradient(masks[k]),
            n_denom=meta[k][0], group_axes=meta[k][1],
        )
        for k, t in trainable["reps"].items()
    }


def bsq_loss(trainable, masks, batch, ctx: BSQTrainContext):
    reps = _reps_from_state(trainable, masks, ctx.meta)
    w = bsq_mod.reconstruct(reps, ctx.bsq_cfg)
    params = bsq_mod.merge_params(ctx.template, w, trainable["float"])
    task_loss, metrics = transformer.loss_fn(params, batch, ctx.cfg)
    reg = bsq_mod.regularizer(reps, ctx.bsq_cfg, ctx.total_quant_params)
    total = task_loss + ctx.bsq_cfg.alpha * reg
    metrics = dict(metrics, reg=reg, total=total)
    return total, metrics


def make_bsq_train_step(
    ctx: BSQTrainContext,
    optimizer,
    lr_fn: Callable,
    grad_clip: Optional[float] = 1.0,
    microbatches: int = 1,
    hoist_reconstruct: bool = True,
    decouple_reg_clip: bool = False,
):
    """Returns `train_step(state, batch) -> (state, metrics)` (jit-able).

    ``hoist_reconstruct`` (§Perf H3): with gradient accumulation, the
    bit-plane -> weight reconstruction and its VJP are microbatch-
    invariant, so they are pulled OUT of the microbatch scan — plane
    tensors (2 x n_planes x params f32, the biggest buffers in the step)
    are then read/written once per step instead of once per microbatch.
    Gradients are mathematically identical (linearity of accumulation).
    """

    def single_grads(trainable, masks, batch):
        return jax.value_and_grad(bsq_loss, has_aux=True)(trainable, masks, batch, ctx)

    def hoisted_grads(trainable, masks, batch):
        split = jax.tree.map(
            lambda x: x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:]), batch
        )

        def head(tr):
            reps = _reps_from_state(tr, masks, ctx.meta)
            w = bsq_mod.reconstruct(reps, ctx.bsq_cfg)
            reg = bsq_mod.regularizer(reps, ctx.bsq_cfg, ctx.total_quant_params)
            return w, tr["float"], reg

        (w, fparams, reg), head_vjp = jax.vjp(head, trainable)

        def mb_loss(w_, f_, mb):
            params = bsq_mod.merge_params(ctx.template, w_, f_)
            return transformer.loss_fn(params, mb, ctx.cfg)

        def body(acc, mb):
            (l, m), (gw, gf) = jax.value_and_grad(mb_loss, argnums=(0, 1), has_aux=True)(
                w, fparams, mb
            )
            acc_gw, acc_gf, acc_l, acc_m = acc
            return (
                jax.tree.map(jnp.add, acc_gw, gw),
                jax.tree.map(jnp.add, acc_gf, gf),
                acc_l + l,
                jax.tree.map(jnp.add, acc_m, m),
            ), None

        zeros = (
            jax.tree.map(jnp.zeros_like, w),
            jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), fparams),
            jnp.zeros(()),
            {"ce": jnp.zeros(()), "aux": jnp.zeros(())},
        )
        (gw, gf, l, m), _ = jax.lax.scan(body, zeros, split)
        inv = 1.0 / microbatches
        gw = jax.tree.map(lambda x: x * inv, gw)
        gf = jax.tree.map(lambda x: (x * inv).astype(jnp.float32), gf)
        # one VJP through reconstruct+regulariser for the whole step
        (grads,) = head_vjp((gw, gf, jnp.asarray(ctx.bsq_cfg.alpha, jnp.float32)))
        l = l * inv
        m = jax.tree.map(lambda x: x * inv, m)
        total = l + ctx.bsq_cfg.alpha * reg
        m = dict(m, reg=reg, total=total)
        return (total, m), grads

    def accumulated_grads(trainable, masks, batch):
        if microbatches == 1:
            return single_grads(trainable, masks, batch)
        if hoist_reconstruct:
            return hoisted_grads(trainable, masks, batch)
        split = jax.tree.map(
            lambda x: x.reshape((microbatches, x.shape[0] // microbatches) + x.shape[1:]), batch
        )

        def body(acc, mb):
            (l, m), g = single_grads(trainable, masks, mb)
            acc_g, acc_l, acc_m = acc
            return (
                jax.tree.map(jnp.add, acc_g, g),
                acc_l + l,
                jax.tree.map(jnp.add, acc_m, m),
            ), None

        zeros_g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), trainable)
        zeros_m = {"ce": 0.0, "aux": 0.0, "reg": 0.0, "total": 0.0}
        (g, l, m), _ = jax.lax.scan(body, (zeros_g, jnp.zeros(()), zeros_m), split)
        inv = 1.0 / microbatches
        return (l * inv, jax.tree.map(lambda x: x * inv, m)), jax.tree.map(
            lambda x: x * inv, g
        )

    def reg_only_grads(trainable, masks):
        def reg_loss(tr):
            reps = _reps_from_state(tr, masks, ctx.meta)
            return ctx.bsq_cfg.alpha * bsq_mod.regularizer(
                reps, ctx.bsq_cfg, ctx.total_quant_params)

        return jax.grad(reg_loss)(trainable)

    def train_step(state, batch):
        (loss, metrics), grads = accumulated_grads(state["trainable"], state["masks"], batch)
        if decouple_reg_clip and grad_clip is not None:
            # beyond-paper: clip the TASK gradient only; the regulariser
            # gradient (planes-only, cheap second grad) is added unclipped
            # so compression pressure isn't crushed by the clip budget.
            g_reg = reg_only_grads(state["trainable"], state["masks"])
            g_task = jax.tree.map(jnp.subtract, grads, g_reg)
            g_task, gnorm = clip_by_global_norm(g_task, grad_clip)
            grads = jax.tree.map(jnp.add, g_task, g_reg)
            metrics["grad_norm"] = gnorm
        elif grad_clip is not None:
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
            metrics["grad_norm"] = gnorm
        lr = lr_fn(state["step"])
        new_trainable, new_opt = optimizer.update(grads, state["opt"], state["trainable"], lr)
        # paper §3.1: trim planes to [0, 2] after the update
        reps = _reps_from_state(new_trainable, state["masks"], ctx.meta)
        reps = project_bitplanes(reps)
        for k, r in reps.items():
            new_trainable["reps"][k] = {"wp": r.wp, "wn": r.wn, "scale": r.scale}
        new_state = {
            "trainable": new_trainable,
            "masks": state["masks"],
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        metrics["lr"] = lr
        return new_state, metrics

    return train_step


def make_requant_step(ctx: BSQTrainContext):
    """Jittable periodic re-quantisation + precision adjustment (static mode)."""
    from ..core.requant import requantize_static

    def requant(state):
        reps = _reps_from_state(state["trainable"], state["masks"], ctx.meta)
        new = {k: requantize_static(r) for k, r in reps.items()}
        trainable = dict(state["trainable"])
        trainable["reps"] = {
            k: {"wp": r.wp, "wn": r.wn, "scale": r.scale} for k, r in new.items()
        }
        return dict(state, trainable=trainable, masks={k: r.mask for k, r in new.items()})

    return requant


def state_reps(state, ctx: BSQTrainContext) -> Dict[str, BitRep]:
    return _reps_from_state(state["trainable"], state["masks"], ctx.meta)


# ---------------------------------------------------------------------------
# Abstract (ShapeDtypeStruct) state builders — used by the dry-run: no
# device allocation ever happens for the production-size configs.
# ---------------------------------------------------------------------------


def abstract_bsq_state(cfg: ModelConfig, bsq_cfg: BSQConfig, optimizer, predicate=None):
    """Shapes-only twin of init_bsq_state: (state_sds, ctx)."""
    import functools

    params_sds = jax.eval_shape(
        functools.partial(transformer.init_params, cfg=cfg), jax.random.PRNGKey(0)
    )
    qp, fp = bsq_mod.partition_params(params_sds, predicate or bsq_mod.default_quant_predicate)
    reps_sds = {}
    for name, sds in qp.items():
        ga = bsq_mod.default_group_axes(name, sds)
        n_max = bsq_cfg.planes if bsq_cfg.mode == "static" else bsq_cfg.n_init
        reps_sds[name] = jax.eval_shape(
            functools.partial(
                bsq_mod.decompose, n_bits=bsq_cfg.n_init, group_axes=ga, n_max=n_max
            ),
            jax.ShapeDtypeStruct(sds.shape, jnp.float32),
        )
    meta = {k: (r.n_denom, r.group_axes) for k, r in reps_sds.items()}
    trainable_sds = {
        "reps": {k: {"wp": r.wp, "wn": r.wn, "scale": r.scale} for k, r in reps_sds.items()},
        "float": fp,
    }
    state_sds = {
        "trainable": trainable_sds,
        "masks": {k: r.mask for k, r in reps_sds.items()},
        "opt": jax.eval_shape(optimizer.init, trainable_sds),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }
    import math

    total = sum(int(math.prod(s.shape)) for s in qp.values())
    ctx = BSQTrainContext(
        cfg=cfg, bsq_cfg=bsq_cfg, template=params_sds, meta=meta, total_quant_params=total
    )
    return state_sds, ctx


def abstract_plain_state(cfg: ModelConfig, optimizer):
    import functools

    params_sds = jax.eval_shape(
        functools.partial(transformer.init_params, cfg=cfg), jax.random.PRNGKey(0)
    )
    return {
        "params": params_sds,
        "opt": jax.eval_shape(optimizer.init, params_sds),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def make_compressed_bsq_dp_step(
    ctx: BSQTrainContext,
    optimizer,
    lr_fn: Callable,
    mesh,
    axis: str = "data",
    grad_clip: Optional[float] = None,
):
    """BSQ train step with int8+error-feedback compressed gradient psum.

    Bit-plane gradients are the natural int8 candidates: the planes
    themselves live in {0..2} after projection, so their task+regulariser
    gradients are small-dynamic-range tensors that quantise to 8 bits
    with little information loss — and they are the *largest* leaves in
    the BSQ state (2 x n_planes x params f32), so compressing their
    all-reduce cuts the step's wire traffic by ~4x where it matters.

    Params (trainable tree) replicated; batch sharded over ``axis``; the
    error-feedback residual is genuinely per-shard state (leading shard
    axis).  Returns ``(add_residuals, train_step)`` — call
    ``state = add_residuals(state)`` once on a state built by
    :func:`init_bsq_state` before the first step.
    """
    from ..dist.collectives import init_residuals, tree_compressed_psum_ef
    from jax.sharding import PartitionSpec as P

    n_dp = mesh.shape[axis]

    def add_residuals(state):
        return dict(state, residual=init_residuals(state["trainable"], n_shards=n_dp))

    def per_shard(trainable, masks, residual, batch):
        (loss, metrics), grads = jax.value_and_grad(bsq_loss, has_aux=True)(
            trainable, masks, batch, ctx
        )
        res_local = jax.tree.map(lambda r: r[0], residual)
        grads, new_residual = tree_compressed_psum_ef(grads, res_local, axis)
        loss = jax.lax.pmean(loss, axis)
        metrics = jax.tree.map(lambda v: jax.lax.pmean(v, axis), metrics)
        new_residual = jax.tree.map(lambda r: r[None], new_residual)
        return loss, metrics, grads, new_residual

    sharded = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=(P(), P(), P(), P(axis)), check_vma=False,
    )

    def train_step(state, batch):
        loss, metrics, grads, new_residual = sharded(
            state["trainable"], state["masks"], state["residual"], batch
        )
        if grad_clip is not None:
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
            metrics["grad_norm"] = gnorm
        lr = lr_fn(state["step"])
        new_trainable, new_opt = optimizer.update(grads, state["opt"], state["trainable"], lr)
        # paper §3.1: trim planes to [0, 2] after the update
        reps = _reps_from_state(new_trainable, state["masks"], ctx.meta)
        reps = project_bitplanes(reps)
        for k, r in reps.items():
            new_trainable["reps"][k] = {"wp": r.wp, "wn": r.wn, "scale": r.scale}
        metrics["lr"] = lr
        return {
            "trainable": new_trainable,
            "masks": state["masks"],
            "opt": new_opt,
            "residual": new_residual,
            "step": state["step"] + 1,
        }, metrics

    return add_residuals, train_step


# ---------------------------------------------------------------------------
# Plain (non-BSQ) baseline training
# ---------------------------------------------------------------------------


def init_plain_state(key, cfg: ModelConfig, optimizer):
    params = transformer.init_params(key, cfg)
    return {"params": params, "opt": optimizer.init(params), "step": jnp.zeros((), jnp.int32)}


def make_plain_train_step(cfg: ModelConfig, optimizer, lr_fn, grad_clip: Optional[float] = 1.0):
    def train_step(state, batch):
        def loss(p):
            return transformer.loss_fn(p, batch, cfg)

        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(state["params"])
        if grad_clip is not None:
            grads, gnorm = clip_by_global_norm(grads, grad_clip)
            metrics["grad_norm"] = gnorm
        lr = lr_fn(state["step"])
        new_params, new_opt = optimizer.update(grads, state["opt"], state["params"], lr)
        metrics["total"] = l
        metrics["lr"] = lr
        return {"params": new_params, "opt": new_opt, "step": state["step"] + 1}, metrics

    return train_step


# ---------------------------------------------------------------------------
# Compressed-DP variant (shard_map + int8 error-feedback psum)
# ---------------------------------------------------------------------------


def make_compressed_dp_step(cfg: ModelConfig, optimizer, lr_fn, mesh, axis="data"):
    """Pure-DP train step with int8+EF gradient all-reduce (dist/collectives).

    Params replicated; batch sharded over `axis`.  State gains a
    "residual" tree (error feedback).  Used by tests and as the §Perf
    lever for collective-bound cells.
    """
    from ..dist.collectives import dp_shard_map, init_residuals, tree_compressed_psum_ef

    n_dp = mesh.shape[axis]

    def init_state(key):
        params = transformer.init_params(key, cfg)
        # error-feedback residual is genuinely per-DP-shard state: leading
        # shard axis, sharded over `axis`.
        residual = init_residuals(params, n_shards=n_dp)
        return {
            "params": params,
            "opt": optimizer.init(params),
            "residual": residual,
            "step": jnp.zeros((), jnp.int32),
        }

    def per_shard(params, residual, batch):
        def loss(p):
            return transformer.loss_fn(p, batch, cfg)

        (l, metrics), grads = jax.value_and_grad(loss, has_aux=True)(params)
        res_local = jax.tree.map(lambda r: r[0], residual)
        grads, new_residual = tree_compressed_psum_ef(grads, res_local, axis)
        l = jax.lax.pmean(l, axis)
        metrics = jax.tree.map(lambda v: jax.lax.pmean(v, axis), metrics)
        new_residual = jax.tree.map(lambda r: r[None], new_residual)
        return l, metrics, grads, new_residual

    sharded = dp_shard_map(per_shard, mesh, axis)

    def train_step(state, batch):
        l, metrics, grads, new_residual = sharded(state["params"], state["residual"], batch)
        lr = lr_fn(state["step"])
        new_params, new_opt = optimizer.update(grads, state["opt"], state["params"], lr)
        return (
            {
                "params": new_params,
                "opt": new_opt,
                "residual": new_residual,
                "step": state["step"] + 1,
            },
            {"total": l, "lr": lr},
        )

    return init_state, train_step
