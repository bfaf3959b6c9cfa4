"""Serve a BSQ-compressed model with batched requests.

    PYTHONPATH=src python examples/serve_quantized.py

Trains briefly with BSQ, freezes + packs the scheme (sign-magnitude
bit-planes), reports the HBM footprint vs bf16, then serves a batch of
prompts through the bucketed engine and prints throughput.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import reduced_config
from repro.core import BSQConfig, export_packed, extract_scheme
from repro.core.bsq import merge_params, reconstruct
from repro.data import MarkovLM
from repro.optim import SGDM, step_decay
from repro.serve import Request, ServeEngine
from repro.train.step import (
    init_bsq_state,
    make_bsq_train_step,
    make_requant_step,
    state_reps,
)


def main():
    cfg = reduced_config("granite-3-2b")
    bsq_cfg = BSQConfig(n_init=8, alpha=0.3, mode="static", compute_dtype=jnp.float32)
    opt = SGDM()
    state, ctx = init_bsq_state(jax.random.PRNGKey(0), cfg, bsq_cfg, opt)
    step = jax.jit(make_bsq_train_step(ctx, opt, step_decay(0.5, [100])))
    requant = jax.jit(make_requant_step(ctx))
    task = MarkovLM(vocab=cfg.vocab_size, seed=7)
    rng = np.random.default_rng(0)
    for i in range(120):
        state, m = step(state, {k: jnp.asarray(v) for k, v in task.batch(rng, 8, 32).items()})
        if (i + 1) % 40 == 0:
            state = requant(state)
    state = requant(state)
    reps = state_reps(state, ctx)
    scheme = extract_scheme(reps)
    print(f"BSQ scheme: bits/para={scheme.bits_per_param:.2f} comp={scheme.compression:.2f}x")

    packed = export_packed(reps)
    packed_bytes = sum(pw.hbm_bytes() for pw in packed.values())
    bf16_bytes = scheme.quantized_params * 2
    print(f"packed weights: {packed_bytes/1e6:.2f} MB vs bf16 {bf16_bytes/1e6:.2f} MB "
          f"({bf16_bytes/max(packed_bytes,1):.2f}x smaller)")

    params = merge_params(ctx.template, reconstruct(reps, bsq_cfg),
                          state["trainable"]["float"])
    engine = ServeEngine(params, cfg, max_len=128)
    prompts = [task.sample(np.random.default_rng(i), 1, 16)[0, :16].astype(np.int32)
               for i in range(8)]
    reqs = [Request(uid=i, tokens=p, max_new=32) for i, p in enumerate(prompts)]
    results = engine.generate(reqs)
    for r in results[:3]:
        print(f"req {r.uid}: prefill {r.prefill_ms:.1f} ms -> {r.tokens[:10]}...")
    toks = sum(len(r.tokens) for r in results)
    print(f"generated {toks} tokens across {len(results)} requests")


if __name__ == "__main__":
    main()
