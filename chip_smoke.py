#!/usr/bin/env python3
"""Chip smoke test: granite-3-2b at its published widths, served on a TPU
through the normal entry points, checked against a reference.

    python3 chip_smoke.py                 # one chip: serve, logits, train
    python3 chip_smoke.py --four-chips    # 2x2 mesh serve vs one device

One chip (the default) runs three phases in this process:

1. serve: ``repro.launch.serve.main`` with ``--full --continuous --paged
   --paged-kernel --packed-bits 6`` (chunked prefill, 8 slots) answers 8
   MarkovLM prompts of 128-512 tokens with 32 new tokens each.  The
   compiled decode program must contain ``tpu_custom_call`` (the Pallas
   kernels), so a kernel cannot be quietly replaced by its reference.
2. logits: the model's own prefill-chunk and paged decode functions run
   twice on the same packed weights: the kernel path (Pallas bitserial
   matmul + Pallas paged attention) and the XLA reference path
   (``use_pallas=False``: jnp dequantise-then-matmul, and
   ``paged_kernel=False``: the gather attention), the latter also in
   float32.  Over the first decode steps the kernel path may be no
   further from the float32 logits than ``LOGIT_RATIO`` times the XLA
   bfloat16 path.
3. train: ``repro.launch.train.main`` takes a few BSQ steps at the
   published widths, with the depth cut until the compiled step's
   ``memory_analysis()`` fits the chip.  Even one whole layer's BSQ state
   (9 float32 bit planes per sign, momentum and gradients: ~216 bytes a
   weight) plus the tied 49155 x 2048 embedding's does not fit 16 GB, so
   the chip holds its share of a four-chip tensor-parallel deployment.

``--four-chips`` runs only the same packed, paged serve on a 2x2
("data", "model") mesh and on one device, and compares the greedy
tokens.  There the sharded packed matmul may not fall back to gathering
its bytes: that warning is an error.

The script exits non-zero, printing no result, when JAX finds no TPU or
the repository's ``src`` is not beside it.  Its last line is one JSON
object naming the device.  The persistent compilation cache goes where
``JAX_COMPILATION_CACHE_DIR`` says, else to ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import resource
import sys
import time
import warnings
from unittest import mock

N_SLOTS = 8
PROMPT_LEN = 256  # --mixed-lens cycles 128, 256, 384, 512
MAX_NEW = 32
MAX_LEN = 640  # longest prompt + new tokens, rounded up to whole 32-row blocks
N_BITS = 6
SERVE_ARGV = [
    "--arch", "granite-3-2b", "--full", "--continuous", "--paged", "--paged-kernel",
    "--packed-bits", str(N_BITS), "--slots", str(N_SLOTS), "--requests", str(N_SLOTS),
    "--prompt-len", str(PROMPT_LEN), "--mixed-lens", "--max-new", str(MAX_NEW),
    "--max-len", str(MAX_LEN),
]
# Logit agreement.  Three paths see the same packed weights and tokens:
# the kernel path and the XLA reference path in the configuration's
# bfloat16, and the XLA reference in float32 at "highest" matmul
# precision.  Error is |logits - f32| / |f32| over a decode step.  Both
# bfloat16 paths multiply the same exact integer codes and round
# activations to bfloat16 (unit roundoff 2^-9) at the same ops; they
# differ in where they round inside a matmul (the kernel accumulates
# 512-row tiles in float32 and rounds once after the scale) and in the
# softmax summation order of paged attention.  So the kernel path may
# be no further from float32 than LOGIT_RATIO times the XLA bfloat16
# path, plus LOGIT_FLOOR (2^-8, bfloat16's resolution) for when both
# are near exact.  A kernel computing in a coarser format (float8: 2^-4)
# or dropping a bit plane (1/64 of each weight's range) lands beyond.
LOGIT_RATIO = 2.0
LOGIT_FLOOR = 2.0**-8
LOGIT_STEPS = 4
TRAIN_SHARE = 4  # chips sharing each layer in the stated training deployment
TRAIN_DEPTHS = (3, 2, 1)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"[chip_smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def host_rss() -> str:
    """This process's resident host memory in bytes: now, where /proc
    reports it, and at its peak."""
    now = next((int(line.split()[1]) * 1024 for line in open("/proc/self/status")
                if line.startswith("VmRSS:")), None)
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024  # KiB on Linux
    return f"host RSS {now}, host peak RSS {peak}"


def peaks(dev) -> str:
    """The device's peak bytes in use and this process's host memory."""
    return f"peak bytes in use {int(dev.memory_stats()['peak_bytes_in_use'])}, {host_rss()}"


def serve_phase(dev):
    from repro.launch import serve as serve_launch

    t0 = time.perf_counter()
    engine, results = serve_launch.main(SERVE_ARGV)
    wall = time.perf_counter() - t0
    vocab = engine.cfg.vocab_size
    if len(results) != N_SLOTS:
        fail(f"serve: {len(results)} results for {N_SLOTS} requests")
    for r in results:
        toks = r.tokens
        if len(toks) != MAX_NEW or toks.min() < 0 or toks.max() >= vocab:
            fail(f"serve: request {r.uid} returned {len(toks)} tokens in "
                 f"[{toks.min()}, {toks.max()}]")
    text = engine.scheduler.decode_program_text()
    n_custom = text.count("tpu_custom_call")
    if n_custom == 0:
        fail("serve: the compiled decode program has no tpu_custom_call")
    log(f"serve: {len(results)} requests x {MAX_NEW} tokens in {wall:.1f} s "
        f"(compiles included); decode program has {n_custom} tpu_custom_call "
        f"sites; {peaks(dev)}")
    return engine


def logit_programs(cfg, paged_kernel: bool):
    """The jitted prefill-chunk and decode programs of the logits phase.

    Every array, the weights included, is an argument: ``jax.jit`` embeds
    closed-over arrays in the program as constants, which would copy the
    weights into each compiled program (host memory while compiling, HBM
    once loaded)."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer

    cache_dtype = jnp.dtype(cfg.kv_cache_dtype)
    chunk = jax.jit(lambda p, c, t, s, n, bt: transformer.prefill_chunk(
        p, c, t, s, n, cfg, cache_dtype=cache_dtype, block_table=bt))
    step = jax.jit(lambda p, c, t, pos, a, bt: transformer.decode_step(
        p, c, t, pos, cfg, active=a, block_table=bt, paged_kernel=paged_kernel))
    return chunk, step


def logits_phase(dev, engine):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data import MarkovLM
    from repro.kernels import ops
    from repro.models import transformer

    cfg, params = engine.cfg, engine.params
    bs, plen = 32, 128
    nb_lane = -(-(plen + LOGIT_STEPS) // bs)
    table = jnp.arange(N_SLOTS * nb_lane, dtype=jnp.int32).reshape(N_SLOTS, nb_lane)
    prompts = MarkovLM(vocab=cfg.vocab_size, seed=5).sample(
        np.random.default_rng(0), N_SLOTS, plen)[:, :plen]
    prompts = jnp.asarray(prompts, jnp.int32)
    start = jnp.zeros((N_SLOTS,), jnp.int32)
    n_valid = jnp.full((N_SLOTS,), plen, jnp.int32)
    active = jnp.ones((N_SLOTS,), bool)

    def run(cfg, paged_kernel, feed=None):
        cache_dtype = jnp.dtype(cfg.kv_cache_dtype)
        cache = transformer.init_cache(cfg, N_SLOTS, nb_lane * bs, cache_dtype,
                                       paged_blocks=N_SLOTS * nb_lane, block_size=bs)
        chunk, step = logit_programs(cfg, paged_kernel)
        logits, cache = chunk(params, cache, prompts, start, n_valid, table)
        toks, out = [], []
        for i in range(LOGIT_STEPS):
            tok = feed[i] if feed is not None else jnp.argmax(logits, -1).astype(jnp.int32)
            toks.append(tok)
            pos = jnp.full((N_SLOTS,), plen + i, jnp.int32)
            args = (params, cache, tok[:, None], pos, active, table)
            if i == 0:
                n_custom = step.lower(*args).as_text().count("tpu_custom_call")
            logits, cache = step(*args)
            out.append(np.asarray(logits[:, : cfg.vocab_size], np.float32))
        return toks, out, n_custom

    t0 = time.perf_counter()
    toks, kern, n_kernel = run(cfg, paged_kernel=True)
    # The references trace the same functions as if no TPU were present,
    # so ops.bitserial_matmul takes its jnp path (use_pallas=False), and
    # paged_kernel=False takes the gather attention.  Tokens are fed from
    # the kernel path so all three see the same inputs.
    with mock.patch.object(ops, "_on_tpu", return_value=False):
        _, xla, n_xla = run(cfg, paged_kernel=False, feed=toks)
        cfg32 = dataclasses.replace(cfg, dtype="float32", kv_cache_dtype="float32")
        with jax.default_matmul_precision("highest"):
            _, f32, _ = run(cfg32, paged_kernel=False, feed=toks)
    if n_kernel == 0 or n_xla != 0:
        fail(f"logits: kernel decode has {n_kernel} tpu_custom_call sites, the "
             f"XLA reference {n_xla}; want some and none")
    rel = lambda a, r: float(np.linalg.norm(a - r) / np.linalg.norm(r))
    for i, (k, x, r) in enumerate(zip(kern, xla, f32)):
        if not (np.isfinite(k).all() and np.isfinite(x).all() and np.isfinite(r).all()):
            fail(f"logits: non-finite logits at decode step {i}")
        err_k, err_x = rel(k, r), rel(x, r)
        log(f"logits: decode step {i}: |kernel - f32| / |f32| = {err_k:.4g}, "
            f"|xla bf16 - f32| / |f32| = {err_x:.4g}, |kernel - xla| / |xla| = "
            f"{rel(k, x):.4g}")
        if err_k > LOGIT_RATIO * err_x + LOGIT_FLOOR:
            fail(f"logits: at decode step {i} the kernel path is {err_k:.4g} from the "
                 f"float32 reference, more than {LOGIT_RATIO} x the XLA bfloat16 "
                 f"path's {err_x:.4g} + {LOGIT_FLOOR}")
    log(f"logits: {LOGIT_STEPS} decode steps agree ({time.perf_counter() - t0:.1f} s); "
        f"{peaks(dev)}")


def train_fits(dev, layers: int) -> bool:
    """Compile the BSQ step and requantisation the train launcher builds,
    at this depth, and compare the larger memory_analysis() with the
    chip's free memory."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, tp_share
    from repro.core import BSQConfig
    from repro.optim import SGDM, step_decay
    from repro.train.step import (abstract_bsq_state, make_bsq_train_step,
                                  make_requant_step)

    cfg = dataclasses.replace(tp_share(get_config("granite-3-2b"), TRAIN_SHARE),
                              n_layers=layers)
    opt = SGDM()
    bsq_cfg = BSQConfig(n_init=8, alpha=5e-3, mode="static", compute_dtype=jnp.bfloat16)
    state, ctx = abstract_bsq_state(cfg, bsq_cfg, opt)
    batch = {k: jax.ShapeDtypeStruct((8, 256), jnp.int32) for k in ("tokens", "labels")}
    step = jax.jit(make_bsq_train_step(ctx, opt, step_decay(0.2, [2, 2])),
                   donate_argnums=0)
    requant = jax.jit(make_requant_step(ctx), donate_argnums=0)
    need = 0
    for lowered in (step.lower(state, batch), requant.lower(state)):
        ma = lowered.compile().memory_analysis()
        need = max(need, ma.argument_size_in_bytes + ma.output_size_in_bytes
                   - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    stats = dev.memory_stats()
    free = int(stats["bytes_limit"]) - int(stats["bytes_in_use"])
    log(f"train: {layers} layers need {need} of {free} free bytes")
    return need <= free


def train_phase(dev):
    import numpy as np

    from repro.launch import train as train_launch

    layers = next((n for n in TRAIN_DEPTHS if train_fits(dev, n)), None)
    if layers is None:
        fail(f"train: no depth in {TRAIN_DEPTHS} fits the chip")
    log(f"train: depth cut to {layers} of 40 layers; this chip holds 1/{TRAIN_SHARE} "
        "of every layer's heads, FFN columns and vocabulary rows")
    out = train_launch.main([
        "--arch", "granite-3-2b", "--full", "--layers", str(layers),
        "--tp-share", str(TRAIN_SHARE), "--steps", "3", "--batch", "8",
        "--seq", "256", "--requant-interval", "1000", "--ckpt-interval", "1000",
    ])
    losses = [rec["total"] for rec in out["history"]]
    if not losses or not np.isfinite(losses).all():
        fail(f"train: non-finite loss {losses}")
    log(f"train: 3 BSQ steps, loss {losses[-1]:.4f}; {peaks(dev)}")


def four_chip_phase():
    import numpy as np

    from repro.kernels.ops import ShardFallbackWarning
    from repro.launch import serve as serve_launch

    warnings.simplefilter("error", ShardFallbackWarning)
    t0 = time.perf_counter()
    engine, sharded = serve_launch.main(
        SERVE_ARGV + ["--data-parallel", "2", "--model-parallel", "2"])
    del engine
    gc.collect()
    log(f"four chips: 2x2 mesh served in {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    engine, single = serve_launch.main(SERVE_ARGV)
    del engine
    log(f"four chips: one device served in {time.perf_counter() - t0:.1f} s")
    a = {r.uid: r.tokens for r in sharded}
    b = {r.uid: r.tokens for r in single}
    same = sum(int(np.array_equal(a[u], b[u])) for u in b)
    log(f"four chips: {same}/{len(b)} requests token-identical to one device")
    if same != len(b) or a.keys() != b.keys():
        for u in sorted(b):
            first = int(np.argmax(a[u] != b[u])) if not np.array_equal(a[u], b[u]) else -1
            log(f"four chips: request {u} first differs at token {first}")
        fail("four chips: the 2x2 mesh tokens differ from one device's")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve on a 2x2 mesh and compare with one device")
    args = ap.parse_args(argv)

    src = pathlib.Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        fail(f"the repository's package is not at {src}")
    sys.path.insert(0, str(src))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"JAX finds no TPU (device 0 is {dev.platform}); nothing runs elsewhere")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        fail(f"need {want} chips, JAX finds {len(devices)}")
    from repro.launch.cache import setup_compilation_cache

    log(f"{len(devices)} x {dev.device_kind}; compile cache {setup_compilation_cache()}; "
        f"{host_rss()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        engine = serve_phase(dev)
        logits_phase(dev, engine)
        del engine
        gc.collect()  # the engine and its scheduler refer to each other
        train_phase(dev)
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
