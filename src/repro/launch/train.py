"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --steps 200 --alpha 5e-3 --workdir /tmp/run1 \
        [--full [--layers L] [--tp-share N]] \
        [--data-parallel N --model-parallel M] [--technique bsq|plain]

On a real fleet this runs once per host (jax.distributed.initialize is
called when JAX_COORDINATOR is set); otherwise it runs on however many
local devices exist.  The default is the reduced smoke-size config, so
the full loop (BSQ + requant + checkpoint + straggler monitor) runs on a
CPU; ``--full`` trains the published widths, ``--layers`` cuts the depth
and ``--tp-share N`` holds one chip's share of every layer of an N-way
tensor-parallel deployment (``configs.tp_share``).
"""
import argparse
import dataclasses
import os

import jax
import jax.numpy as jnp


def main(argv=None):
    """Parse ``argv`` (default: the command line) and train.  Returns the
    trainer's result: ``dict(state=, history=, scheme=)`` for BSQ, and
    ``dict(state=, history=)`` for plain training."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--technique", default="bsq", choices=["bsq", "plain"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--alpha", type=float, default=5e-3)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--requant-interval", type=int, default=50)
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers (0 = the config's)")
    ap.add_argument("--tp-share", type=int, default=1,
                    help="hold one chip's share of a deployment that divides "
                         "every layer this many ways (heads, FFN columns, "
                         "vocabulary rows); widths stay as published")
    ap.add_argument("--data-parallel", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=0)
    ap.add_argument("--optimizer", default="sgdm", choices=["sgdm", "adamw"])
    args = ap.parse_args(argv)

    if os.environ.get("JAX_COORDINATOR"):
        jax.distributed.initialize()  # multi-host fleet entry

    from ..configs import get_config, reduced_config, tp_share
    from ..core import BSQConfig
    from ..data import MarkovLM, sharded_lm_iterator
    from ..dist import elastic, sharding as dist_sharding
    from ..optim import SGDM, AdamW, step_decay
    from ..train.step import (
        init_bsq_state,
        init_plain_state,
        make_bsq_train_step,
        make_plain_train_step,
        make_requant_step,
    )
    from ..train.trainer import TrainerConfig, simple_train_loop, train_bsq
    from .cache import setup_compilation_cache
    from .mesh import make_mesh

    setup_compilation_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    cfg = tp_share(cfg, args.tp_share)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    opt = SGDM() if args.optimizer == "sgdm" else AdamW()
    lr_fn = step_decay(args.lr, [int(args.steps * 0.7), int(args.steps * 0.9)])

    # optional explicit mesh + sharded state placement (rules: repro.dist)
    mesh = None
    batch_sharding = None
    if args.data_parallel and args.model_parallel:
        mesh = make_mesh((args.data_parallel, args.model_parallel), ("data", "model"))
        if not elastic.validate_batch_divisibility(args.batch, mesh):
            raise SystemExit(
                f"--batch {args.batch} does not divide over the mesh's data axes "
                f"({dict(mesh.shape)}); pick a batch the DP axes divide"
            )
        batch_sharding = dist_sharding.tree_shardings(
            mesh, dist_sharding.data_batch_spec(mesh, args.batch, 2)
        )

    task = MarkovLM(vocab=cfg.vocab_size, seed=13)
    data = sharded_lm_iterator(task, args.batch, args.seq, seed=0, sharding=batch_sharding)
    tcfg = TrainerConfig(
        total_steps=args.steps, requant_interval=args.requant_interval,
        ckpt_interval=args.ckpt_interval, log_interval=10, workdir=args.workdir,
    )

    if args.technique == "bsq":
        bsq_cfg = BSQConfig(n_init=8, alpha=args.alpha, mode="static",
                            compute_dtype=jnp.float32 if args.reduced else jnp.bfloat16)
        state, ctx = init_bsq_state(jax.random.PRNGKey(0), cfg, bsq_cfg, opt)
        if mesh is not None:
            state = elastic.reshard_tree(state, mesh)
        step = jax.jit(make_bsq_train_step(ctx, opt, lr_fn), donate_argnums=0)
        # Donated like the step: the trainer keeps only the requantised
        # state, and an undonated requant holds two copies of the planes.
        requant = jax.jit(make_requant_step(ctx), donate_argnums=0)
        out = train_bsq(state, ctx, step, requant, data, tcfg, mesh=mesh)
        s = out["scheme"]
        print(f"done: bits/para={s.bits_per_param:.2f} comp={s.compression:.2f}x")
        return out
    else:
        state = init_plain_state(jax.random.PRNGKey(0), cfg, opt)
        if mesh is not None:
            state = elastic.reshard_tree(state, mesh)
        step = jax.jit(make_plain_train_step(cfg, opt, lr_fn), donate_argnums=0)
        state, history = simple_train_loop(state, step, data, args.steps)
        print(f"done: final={history[-1]}")
        return {"state": state, "history": history}


if __name__ == "__main__":
    main()
