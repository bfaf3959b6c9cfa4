"""Serving-throughput benchmark: continuous batching vs length-bucketing.

Workload: a mixed prompt-length request set with staggered (Poisson)
arrivals — the regime where bucketing fragments into many small batches
and a scalar shared position wastes the throughput BSQ's packed weights
buy back.  Both engines serve the SAME request set; the bucketed
baseline gets offline semantics (all requests present up front, no
arrival penalty), the continuous engine additionally respects the
arrival times — so a continuous win understates the real gap.

Emits harness CSV rows (``name,us_per_call,derived``)::

    serve_bucketed,<us_total>,toks_per_s=...;programs=...
    serve_continuous,<us_total>,toks_per_s=...;occupancy=...;programs=1

Both runs are executed twice and the second (post-warmup) run is timed,
so compile time is excluded and the continuous row doubles as the
no-recompile check: ``programs`` must not grow between the runs.

``--smoke`` shrinks the workload for CI (the scheduler hot path is then
exercised on every PR) and asserts the invariants instead of just
printing them.

Packed-sharded mode: ``--packed-bits N`` serves the same workload over a
bit-plane-packed model (``core.packing.pack_model_params``); adding
``--data-parallel D --model-parallel M`` runs it on a (D, M) host-device
mesh with the packed bytes sharded per the dist rules, checks token
identity against the single-device packed engine, and emits a
``serve_packed_hbm`` row showing per-device packed memory dropping by
the model-axis factor::

    serve_packed_hbm,<us>,global_bytes=...;per_dev_bytes=...;shrink_x=...

``--chunked-prefill`` additionally serves the workload through the
chunked-prefill scheduler (fused multi-admit + interleaved prefill/decode)
and emits a ``serve_prefill`` row per prefill style — TTFT percentiles
(admission burst -> first token; the legacy numbers include the
serialisation behind earlier batch-1 prefills in the same burst, which is
the cost multi-admit removes) and compiled-program counts (legacy grows
with the number of distinct prompt lengths; chunked is bounded by the
chunk-size table)::

    serve_prefill,<us_total>,mode=legacy;ttft_p50_ms=...;ttft_p95_ms=...;prefill_programs=...
    serve_prefill,<us_total>,mode=chunked;ttft_p50_ms=...;ttft_p95_ms=...;prefill_programs=...

``--paged`` additionally serves the workload through the paged-KV
scheduler — block pool sized to the workload's live tokens (sum of the
``n_slots`` largest per-request block needs) instead of
``n_slots * max_len`` rows — checks token identity against the bucketed
reference, and emits a ``serve_paged_hbm`` row with the cache-memory
shrink plus block-occupancy/fragmentation telemetry::

    serve_paged_hbm,<us_total>,block_size=...;n_blocks=...;cache_bytes=...;unpaged_cache_bytes=...;shrink_x=...;block_occupancy=...;fragmentation=...;leaked_blocks=0;tpot_p50_ms=...;tpot_p95_ms=...;attn_read_bytes_per_step=...

``--paged-kernel`` (with ``--paged``) additionally serves through the
Pallas block-table-walking decode kernel, checks token identity against
the same bucketed reference, and emits a ``serve_paged_kernel`` row:
decode TPOT p50/p95 plus an attention-HBM-read estimate per decode step
— the kernel reads only live blocks (the scheduler's block-read trace)
where the gather path reads every lane's full pool view, so
``read_shrink_x`` is the per-step KV-byte reduction the kernel buys::

    serve_paged_kernel,<us_total>,block_size=...;table_shards=...;tpot_p50_ms=...;tpot_p95_ms=...;attn_read_bytes_per_step=...;gather_read_bytes_per_step=...;read_shrink_x=...

``--overload`` (with ``--paged``) runs an open-loop overload sweep: the
same request set with SLO tiers (every 4th request ``latency``, the rest
``throughput``) is replayed at increasing arrival rates through a
deliberately tight block pool (~60% of the workload's resident-set
sizing) with ``overcommit=2.0``, so past saturation the scheduler must
preempt-and-recompute to keep admitting.  One row per offered rate::

    serve_overload,<us_total>,rate=...;goodput_tok_s=...;preemptions=...;preempted_rows=...;latency_p99_ttft_ms=...;throughput_p99_ttft_ms=...;latency_p99_tpot_ms=...;throughput_p99_tpot_ms=...;leaked_blocks=0

TTFT here is end-to-end (``enqueued -> first_token``, so queue wait and
pre-first-token requeue stalls count); TPOT is ``first_token ->
finished`` over the decoded tokens (post-first-token preemption stalls
count).  Every rate's outputs are checked token-identical to the
bucketed reference — preemption must never change a greedy token.
Under ``--smoke`` the sweep additionally asserts graceful degradation:
goodput at the top rate stays within 2.5x of the sweep's best, the top
rate actually preempts (counters visible), and the latency tier's p99
TTFT beats the throughput tier's.

``--spec-decode`` (with ``--paged`` and ``--packed-bits``) serves the
workload through bit-plane speculative decoding at each draft depth in
the ``draft_planes`` sweep {1, 2, 3} — ONE engine serves the whole
sweep (the plane count is a runtime operand into the draft-step
program, so changing it compiles nothing) — checks token identity
against the bucketed reference at every point, and emits one
``serve_spec`` row per draft depth::

    serve_spec,<us_total>,draft_planes=...;gamma=...;accept_rate=...;rounds=...;committed=...;toks_per_s=...;speedup_x=...;spec_programs=...;leaked_blocks=0

``speedup_x`` is tokens/sec against the non-speculative paged run of
the same packed engine; under ``--smoke`` the best sweep point must
clear 1.2x and the whole sweep must stay within ``gamma`` compiled
programs (it compiles exactly 2: one draft step reused at every round
depth and precision level, plus one fixed-width verify chunk).

``--degrade`` (with ``--overload`` and ``--packed-bits``) replays the
overload sweep through the SAME tight pool with the load-triggered
degrade loop armed: under pressure the scheduler sheds active bit
planes (every token gets cheaper) before shedding requests
(preemption/recompute), restoring with hysteresis as the queue drains.
One ``serve_degrade`` row per offered rate, with the rate-matched
no-degrade overload goodput as the request-shedding baseline::

    serve_degrade,<us_total>,rate=...;goodput_tok_s=...;baseline_goodput_tok_s=...;sheds=...;restores=...;preemptions=...;min_active_planes=...;leaked_blocks=0

Under ``--smoke`` the sweep must shed AND restore, drain with zero
leaks, never recompile (the plane count is a runtime operand), and hold
goodput within 25% of the baseline — a regression floor, not a speedup
claim: the CPU reference bitserial path masks planes in a statically
unrolled loop, so fewer active planes save no host compute; on TPU the
shed planes cut HBM weight traffic directly.

``--json PATH`` dumps a stable, versioned JSON document
(``schema_version`` 1): the emitted rows, a metrics-registry snapshot
per serving mode (the same counters/histograms ``launch.serve
--metrics-port`` scrapes — every derived row statistic is recomputable
from it), and the quantization-quality probe rows when ``--packed-bits``
is set (``repro.obs.quality``: logit MSE + top-1 agreement per active
plane count).  CI uploads it as the ``BENCH_serve.json`` artifact and
re-validates it with :func:`validate_bench_json`.  Versioning policy
(see ``BENCH_JSON_KEYS``): new top-level keys with neutral defaults are
ADDITIVE and keep ``schema_version`` 1 — consumers must tolerate
unknown keys; renaming/removing/retyping an existing key bumps it.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np


def build_workload(cfg, n_requests: int, max_new: int, rate: float, seed: int = 0):
    """Mixed-length prompts + Poisson arrival steps (seeded)."""
    from repro.launch.serve import poisson_arrivals

    rng = np.random.default_rng(seed)
    # Near-unique prompt lengths: the realistic mixed-traffic regime, and
    # the worst case for bucketing (every bucket degenerates to batch 1).
    lens = [4 + 2 * i for i in range(n_requests)]
    prompts = [
        rng.integers(0, cfg.vocab_size, size=lens[i]).astype(np.int32)
        for i in range(n_requests)
    ]

    def reqs():
        from repro.serve import Request

        return [
            Request(uid=i, tokens=prompts[i], max_new=max_new)
            for i in range(n_requests)
        ]

    return reqs, poisson_arrivals(n_requests, rate, seed=seed)


def packed_hbm_stats(engine):
    """(global_bytes, per_device_bytes) of the engine's packed weights."""
    from repro.core.packing import packed_leaves

    glob = per_dev = 0
    for pw in packed_leaves(engine.params):
        for arr in (pw.planes, pw.sign, pw.scale):
            glob += arr.nbytes
            shards = getattr(arr, "addressable_shards", None)
            per_dev += shards[0].data.nbytes if shards else arr.nbytes
    return glob, per_dev


def run_bucketed(params, cfg, reqs, max_len: int):
    # Always single-device: the bucketed run is the token-identity
    # reference the continuous (possibly mesh-sharded) run is checked
    # against.
    from repro.serve import ServeEngine

    engine = ServeEngine(params, cfg, max_len=max_len)
    engine.generate(reqs())  # warmup: compile every bucket's programs
    t0 = time.perf_counter()
    results = engine.generate(reqs())
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results)
    # _prefill_cache is keyed by batch size, but each jitted entry retraces
    # per prompt-length shape — sum the real compiled-program counts.
    programs = sum(int(fn._cache_size()) for fn in engine._prefill_cache.values())
    return results, wall, toks, programs


def cache_bytes(pool) -> int:
    """Total bytes of the pool's decode-cache arrays (paged: the block
    pool replaces the per-lane max_len reservation)."""
    import jax

    return sum(leaf.nbytes for leaf in jax.tree.leaves(pool.cache))


def paged_pool_size(reqs, n_slots: int, block_size: int) -> int:
    """Size the block pool to the workload: the sum of the n_slots
    largest per-request lifetime block needs — enough commit capacity
    for any concurrent resident set, far below slots * max_len."""
    from repro.serve import BlockAllocator

    rows = BlockAllocator(1, block_size).blocks_for_rows  # one source of truth
    needs = sorted((rows(len(r.tokens) + r.max_new - 1) for r in reqs),
                   reverse=True)
    return sum(needs[:n_slots])


def run_continuous(params, cfg, reqs, arrivals, max_len: int, n_slots: int, mesh=None,
                   chunked: bool = False, paged: bool = False, block_size: int = 8,
                   n_blocks=None, paged_kernel: bool = False):
    from repro.serve import ServeEngine

    engine = ServeEngine(params, cfg, max_len=max_len, continuous=True, n_slots=n_slots,
                         mesh=mesh, chunked_prefill=chunked, paged=paged,
                         block_size=block_size, n_blocks=n_blocks,
                         paged_kernel=paged_kernel)
    sched = engine.scheduler
    engine.generate(reqs(), arrival_steps=arrivals)  # warmup
    programs_after_warmup = (sched.compiled_decode_programs(),
                             sched.compiled_prefill_programs())
    sched.pool.reset()
    sched.reset_telemetry()  # zero the obs registry + flight recorder
    t0 = time.perf_counter()
    results = engine.generate(reqs(), arrival_steps=arrivals)
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results)
    assert (sched.compiled_decode_programs(),
            sched.compiled_prefill_programs()) == programs_after_warmup, (
        "decode/prefill recompiled after warmup"
    )
    return results, wall, toks, sched


def ttft_stats(results):
    """(p50, p95) of per-request TTFT in ms (Result.prefill_ms)."""
    ttfts = np.asarray([r.prefill_ms for r in results])
    return float(np.percentile(ttfts, 50)), float(np.percentile(ttfts, 95))


def tpot_stats(sched):
    """(p50, p95) in ms of the scheduler's step, the host time between a
    decoding lane's tokens, from its ``serve_step_ms`` histogram (same
    interpolation as numpy.percentile over the retained reservoir)."""
    h = sched.obs.registry.histogram("serve_step_ms")
    return h.percentile(50), h.percentile(95)


def attn_read_bytes_per_step(cfg, sched, kernel: bool) -> int:
    """Estimated attention KV HBM reads per decode step, summed over the
    paged attention layers.  The gather path materialises every lane's
    full table view (n_slots * blocks_per_lane blocks); the kernel walks
    only live blocks (the scheduler's attn_read_blocks_trace)."""
    pool = sched.pool
    bs = pool.block_size
    row_bytes = (cfg.n_kv_heads * cfg.resolved_head_dim
                 * np.dtype(cfg.kv_cache_dtype).itemsize * 2)  # K row + V row
    kinds = [k.split("+")[0] for k in cfg.layer_pattern]
    layers = (kinds.count("attn") * cfg.n_superblocks
              + kinds[: cfg.n_tail_layers].count("attn"))
    if kernel:
        blocks = sched.attn_read_blocks_trace.mean()
    else:
        blocks = pool.n_slots * pool.blocks_per_lane
    return int(blocks * bs * row_bytes * layers)


def overload_tier(uid: int) -> str:
    """The overload sweep's SLO mix: every 4th request is latency-tier."""
    return "latency" if uid % 4 == 0 else "throughput"


def run_overload(params, cfg, reqs, ref, max_len, n_slots, block_size,
                 rates, arrival_seed, smoke):
    """Open-loop overload sweep: replay the tiered workload at each
    offered rate through a tight-pool overcommitted paged engine and
    emit one ``serve_overload`` row per rate.  Returns the last rate's
    scheduler (for the --json registry snapshot)."""
    import dataclasses

    from benchmarks.common import emit
    from repro.launch.serve import poisson_arrivals
    from repro.obs import trace as obs_trace
    from repro.serve import BlockAllocator, ServeEngine

    def tiered():
        return [dataclasses.replace(r, tier=overload_tier(r.uid))
                for r in reqs()]

    base = tiered()
    # Tight pool: ~60% of the resident-set sizing the plain --paged run
    # uses, floored at the largest single request's lifetime need (the
    # up-front rejection rule must still admit every request).
    rows = BlockAllocator(1, block_size).blocks_for_rows
    max_need = max(rows(len(r.tokens) + r.max_new - 1) for r in base)
    n_blocks = max(int(0.6 * paged_pool_size(base, n_slots, block_size)),
                   max_need)
    engine = ServeEngine(params, cfg, max_len=max_len, continuous=True,
                         n_slots=n_slots, paged=True, block_size=block_size,
                         n_blocks=n_blocks, overcommit=2.0)
    sched = engine.scheduler
    # One warmup pass compiles the chunk/decode programs for the sweep.
    engine.generate(tiered(),
                    arrival_steps=poisson_arrivals(len(base), rates[0],
                                                   seed=arrival_seed))
    programs = (sched.compiled_decode_programs(),
                sched.compiled_prefill_programs())

    stats = []
    for rate in rates:
        sched.pool.reset()
        sched.reset_telemetry()
        arrivals = poisson_arrivals(len(base), rate, seed=arrival_seed)
        t0 = time.perf_counter()
        results = engine.generate(tiered(), arrival_steps=arrivals)
        wall = time.perf_counter() - t0
        # Preemption must never change a greedy token, at any rate.
        for r in results:
            np.testing.assert_array_equal(ref[r.uid], r.tokens)
        alloc = sched.pool.allocator
        leaked = alloc.n_blocks - alloc.free_count
        assert leaked == 0, f"rate={rate}: {leaked} blocks leaked"
        assert alloc.committed == 0, (rate, alloc.committed)
        assert not sched.obs.recorder.leaked, sched.obs.recorder.leaked
        assert (sched.compiled_decode_programs(),
                sched.compiled_prefill_programs()) == programs, (
            "overload sweep recompiled after warmup")

        n_toks = {r.uid: len(r.tokens) for r in results}
        ttft = {"latency": [], "throughput": []}
        tpot = {"latency": [], "throughput": []}
        for tr in sched.obs.recorder.traces():
            tier = overload_tier(tr.uid)
            e2e = tr.span_ms(obs_trace.ENQUEUED, obs_trace.FIRST_TOKEN)
            if e2e is not None:
                ttft[tier].append(e2e)
            ft, term = tr.find(obs_trace.FIRST_TOKEN), tr.terminal
            n = n_toks.get(tr.uid, 0)
            if ft is not None and term is not None and n > 1:
                tpot[tier].append((term.ts - ft.ts) * 1e3 / (n - 1))
        p99 = {k: {t: float(np.percentile(v, 99)) if v else float("nan")
                   for t, v in d.items()}
               for k, d in (("ttft", ttft), ("tpot", tpot))}
        goodput = sum(n_toks.values()) / wall
        preempts = sched.preemptions_total()
        stats.append({"rate": rate, "goodput": goodput,
                      "preemptions": preempts, "p99": p99})
        emit("serve_overload", wall * 1e6,
             f"rate={rate:g};goodput_tok_s={goodput:.1f};"
             f"preemptions={preempts};"
             f"preempted_rows={int(sched._c_preempt_rows.value)};"
             f"n_blocks={n_blocks};overcommit=2.0;"
             f"latency_p99_ttft_ms={p99['ttft']['latency']:.2f};"
             f"throughput_p99_ttft_ms={p99['ttft']['throughput']:.2f};"
             f"latency_p99_tpot_ms={p99['tpot']['latency']:.2f};"
             f"throughput_p99_tpot_ms={p99['tpot']['throughput']:.2f};"
             f"leaked_blocks={leaked}")

    if smoke:
        top = stats[-1]
        best = max(s["goodput"] for s in stats)
        # Graceful degradation: past saturation the engine keeps
        # producing, it doesn't collapse under preemption churn.
        assert top["goodput"] >= 0.4 * best, (
            f"goodput collapsed past saturation: {top['goodput']:.1f} tok/s "
            f"at rate {top['rate']:g} vs best {best:.1f}")
        # The top rate must actually exercise preemption (counters
        # visible) ...
        assert top["preemptions"] > 0, "overload never preempted"
        # ... and the latency tier must see it later/less: priority
        # admission + preempt-throughput-first ⇒ better e2e p99 TTFT.
        assert (top["p99"]["ttft"]["latency"]
                < top["p99"]["ttft"]["throughput"]), top["p99"]
    return sched, stats


def run_degrade(params, cfg, reqs, max_len, n_slots, block_size, rates,
                arrival_seed, baseline_stats, smoke):
    """Degrade overload sweep: the same tiered workload, tight pool, and
    offered rates as :func:`run_overload`, but with the load-triggered
    degrade loop armed — under pressure the scheduler sheds bit planes
    (cheaper tokens) before shedding requests (preemption/recompute).
    One ``serve_degrade`` row per rate, with the rate-matched no-degrade
    overload stats as the request-shedding baseline.  Returns the last
    rate's scheduler plus the per-rate stats for the --json document."""
    import dataclasses

    from benchmarks.common import emit
    from repro.launch.serve import poisson_arrivals
    from repro.serve import BlockAllocator, ServeEngine

    def tiered():
        return [dataclasses.replace(r, tier=overload_tier(r.uid))
                for r in reqs()]

    base = tiered()
    # Identical pool sizing to run_overload: the comparison isolates the
    # degrade loop, not the pool geometry.
    rows = BlockAllocator(1, block_size).blocks_for_rows
    max_need = max(rows(len(r.tokens) + r.max_new - 1) for r in base)
    n_blocks = max(int(0.6 * paged_pool_size(base, n_slots, block_size)),
                   max_need)
    # hysteresis 2: the bench schedules' calm tails are short, and the
    # row should show the restore path, not just the shed ramp
    engine = ServeEngine(params, cfg, max_len=max_len, continuous=True,
                         n_slots=n_slots, paged=True, block_size=block_size,
                         n_blocks=n_blocks, overcommit=2.0, degrade=True,
                         degrade_queue_depth=1, degrade_hysteresis=2)
    sched = engine.scheduler
    engine.generate(tiered(),
                    arrival_steps=poisson_arrivals(len(base), rates[0],
                                                   seed=arrival_seed))
    programs = (sched.compiled_decode_programs(),
                sched.compiled_prefill_programs())

    baseline_by_rate = {s["rate"]: s["goodput"] for s in baseline_stats}
    stats = []
    for rate in rates:
        sched.pool.reset()
        sched.reset_telemetry()
        arrivals = poisson_arrivals(len(base), rate, seed=arrival_seed)
        t0 = time.perf_counter()
        results = engine.generate(tiered(), arrival_steps=arrivals)
        wall = time.perf_counter() - t0
        # Degraded tokens legitimately differ from the full-precision
        # reference — token consistency vs the logged plane counts is the
        # conformance suite's job (static-truncation replay).  Here the
        # contract is lifecycle + accounting:
        alloc = sched.pool.allocator
        leaked = alloc.n_blocks - alloc.free_count
        assert leaked == 0, f"rate={rate}: {leaked} blocks leaked"
        assert alloc.committed == 0, (rate, alloc.committed)
        assert not sched.obs.recorder.leaked, sched.obs.recorder.leaked
        assert (sched.compiled_decode_programs(),
                sched.compiled_prefill_programs()) == programs, (
            "degrade transitions recompiled a program — the plane count "
            "must stay a runtime operand")
        for r in results:
            assert r.plane_log is not None and len(r.plane_log) == len(r.tokens)
        goodput = sum(len(r.tokens) for r in results) / wall
        min_planes = int(min(min(r.plane_log) for r in results))
        baseline = baseline_by_rate.get(rate, float("nan"))
        stats.append({"rate": rate, "goodput": goodput,
                      "baseline_goodput": baseline,
                      "sheds": sched.degrade_sheds,
                      "restores": sched.degrade_restores,
                      "preemptions": sched.preemptions_total(),
                      "min_active_planes": min_planes})
        emit("serve_degrade", wall * 1e6,
             f"rate={rate:g};goodput_tok_s={goodput:.1f};"
             f"baseline_goodput_tok_s={baseline:.1f};"
             f"sheds={sched.degrade_sheds};restores={sched.degrade_restores};"
             f"preemptions={sched.preemptions_total()};"
             f"min_active_planes={min_planes};"
             f"n_blocks={n_blocks};overcommit=2.0;"
             f"leaked_blocks={leaked}")

    if smoke:
        top = stats[-1]
        # The loop must actually fire both directions across the sweep
        # (the top rate sheds; drain tails restore) ...
        assert sum(s["sheds"] for s in stats) > 0, "degrade never shed a plane"
        assert sum(s["restores"] for s in stats) > 0, "degrade never restored"
        assert top["min_active_planes"] < max(
            s["min_active_planes"] for s in stats) or top["sheds"] > 0
        # ... and shedding planes must not UNDERPERFORM shedding requests.
        # On the CPU reference path the bitserial matmul masks planes in a
        # statically-unrolled loop, so fewer active planes save no compute
        # — the floor is a regression guard (no pathological overhead from
        # plane grouping/bookkeeping), not a speedup claim; on TPU the
        # shed planes cut HBM weight traffic directly.
        assert top["goodput"] >= 0.75 * top["baseline_goodput"], (
            f"degrade goodput {top['goodput']:.1f} tok/s fell more than 25% "
            f"below the request-shedding baseline "
            f"{top['baseline_goodput']:.1f} tok/s at rate {top['rate']:g}")
    return sched, stats


BENCH_JSON_KEYS = {
    # schema_version 1 layout: key -> required type.  VERSIONING POLICY:
    # adding a NEW top-level key (with an empty/neutral default when its
    # flag is off) is additive and does NOT bump schema_version —
    # consumers must tolerate unknown keys.  Renaming, removing, or
    # changing the type/meaning of an existing key is breaking and bumps
    # schema_version.  "overload", "spec", and "degrade" were all added
    # additively under version 1.
    "schema_version": int,
    "workload": dict,
    "rows": list,
    "metrics": dict,
    "quality": list,
    "overload": list,
    "spec": list,
    "degrade": list,
}


def validate_bench_json(doc: dict) -> None:
    """Schema check for the --json document (also run by CI over the
    uploaded artifact): version 1, every required key present with the
    right type, and rows shaped name/us_per_call/derived."""
    if doc.get("schema_version") != 1:
        raise ValueError(f"schema_version {doc.get('schema_version')!r} != 1 "
                         "— breaking layout change without a consumer update?")
    for key, typ in BENCH_JSON_KEYS.items():
        if key not in doc:
            raise ValueError(f"--json document missing required key {key!r}")
        if not isinstance(doc[key], typ):
            raise ValueError(f"--json key {key!r}: expected {typ.__name__}, "
                             f"got {type(doc[key]).__name__}")
    for row in doc["rows"]:
        if set(row) != {"name", "us_per_call", "derived"}:
            raise ValueError(f"malformed bench row {row!r}")
        float(row["us_per_call"])  # numeric


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--arrival-rate", type=float, default=1.0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI workload + hard asserts")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="also serve through the chunked-prefill scheduler "
                         "and emit serve_prefill rows (TTFT + compile counts) "
                         "for legacy vs chunked")
    ap.add_argument("--paged", action="store_true",
                    help="also serve through the paged-KV scheduler (block "
                         "pool sized to the workload) and emit a "
                         "serve_paged_hbm row: cache bytes vs unpaged + "
                         "block occupancy / fragmentation")
    ap.add_argument("--block-size", type=int, default=8,
                    help="KV block rows for --paged")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="with --paged: also serve through the Pallas "
                         "block-table-walking decode kernel, check token "
                         "identity, and emit a serve_paged_kernel row with "
                         "decode TPOT percentiles and the attention-HBM-read "
                         "shrink vs the full-pool gather path")
    ap.add_argument("--overload", action="store_true",
                    help="with --paged: open-loop overload sweep through a "
                         "tight-pool overcommit=2.0 engine with SLO tiers — "
                         "one serve_overload row (goodput + per-tier p99 "
                         "TTFT/TPOT + preemption counters) per offered rate")
    ap.add_argument("--degrade", action="store_true",
                    help="with --overload and --packed-bits: replay the "
                         "overload sweep through the same tight pool with "
                         "the load-triggered degrade loop armed (shed bit "
                         "planes before shedding requests) — one "
                         "serve_degrade row per rate with shed/restore "
                         "counters and the rate-matched overload goodput as "
                         "the request-shedding baseline")
    ap.add_argument("--spec-decode", action="store_true",
                    help="with --paged and --packed-bits: also serve through "
                         "bit-plane speculative decoding, sweeping the draft "
                         "depth over draft_planes in {1,2,3} on ONE engine "
                         "(runtime plane dispatch — no recompile between "
                         "points), and emit a serve_spec row per depth with "
                         "the acceptance rate and the speedup vs the "
                         "non-speculative paged run")
    ap.add_argument("--gamma", type=int, default=4,
                    help="max draft steps per speculative round "
                         "(--spec-decode)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump all emitted rows as JSON to PATH")
    ap.add_argument("--packed-bits", type=int, default=0,
                    help="serve a bit-plane-packed model at this precision "
                         "(0 = float weights)")
    ap.add_argument("--data-parallel", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=0,
                    help="with --data-parallel: serve on a (D, M) mesh of "
                         "host devices (forces XLA host platform devices); "
                         "with --packed-bits the packed bytes are sharded "
                         "per-device and the HBM shrink factor is emitted")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests, args.max_new, args.slots = 6, 4, 4
    if args.paged_kernel and not args.paged:
        raise SystemExit("--paged-kernel requires --paged")
    if args.overload and not args.paged:
        raise SystemExit("--overload requires --paged")
    if args.spec_decode and not args.paged:
        raise SystemExit("--spec-decode requires --paged")
    if args.degrade and not args.overload:
        raise SystemExit("--degrade requires --overload (the sweep's "
                         "no-degrade run is the request-shedding baseline)")
    if args.degrade and args.packed_bits < 2:
        raise SystemExit("--degrade requires --packed-bits >= 2 (shedding "
                         "truncates the packed weight's bit planes)")
    if args.spec_decode and args.packed_bits < 2:
        raise SystemExit("--spec-decode requires --packed-bits >= 2 (drafting "
                         "truncates the packed weight's bit planes)")
    if args.spec_decode and args.smoke:
        # spec decode amortises dispatches over decode rounds — give the
        # CI workload enough decode steps for the speedup to be signal,
        # not noise, while staying small
        args.max_new = 24
    if args.degrade and args.smoke:
        # the degrade loop needs decode-heavy lanes: pressure steps to
        # ramp the shed and a calm drain tail long enough for the
        # hysteresis to restore
        args.max_new = max(args.max_new, 24)
    if bool(args.data_parallel) != bool(args.model_parallel):
        raise SystemExit("--data-parallel and --model-parallel must be given together")
    n_dev = args.data_parallel * args.model_parallel
    # Fake host devices stand in for a mesh only where no TPU is
    # attached (counted from PCI ids, without initialising jax); the
    # flag must be set before jax initialises.
    from jax._src.hardware_utils import num_available_tpu_chips_and_device_id

    if n_dev > 1 and num_available_tpu_chips_and_device_id()[0] == 0:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n_dev}"
        )

    import jax  # noqa: F401  (defer platform init past argparse)

    from benchmarks.common import emit
    from repro.configs import reduced_config
    from repro.launch.mesh import make_mesh
    from repro.models import init_params

    cfg = reduced_config(args.arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    if args.packed_bits:
        from repro.core.packing import pack_model_params

        params = pack_model_params(params, args.packed_bits)
    mesh = None
    if n_dev > 1:
        mesh = make_mesh((args.data_parallel, args.model_parallel), ("data", "model"))
    reqs, arrivals = build_workload(cfg, args.requests, args.max_new, args.arrival_rate)

    b_results, b_wall, b_toks, b_programs = run_bucketed(params, cfg, reqs, args.max_len)
    c_results, c_wall, c_toks, sched = run_continuous(
        params, cfg, reqs, arrivals, args.max_len, args.slots, mesh=mesh
    )
    # Registry snapshots per serving mode for the --json document (each
    # engine carries its own fresh obs bundle, reset after warmup).
    snapshots = {}
    quality_rows = []
    overload_stats = []
    spec_stats = []
    degrade_stats = []

    # Same requests, greedy: outputs must agree token-for-token.
    ref = {r.uid: r.tokens for r in b_results}
    for r in c_results:
        np.testing.assert_array_equal(ref[r.uid], r.tokens)

    b_tps = b_toks / b_wall
    c_tps = c_toks / c_wall
    emit("serve_bucketed", b_wall * 1e6,
         f"toks_per_s={b_tps:.1f};prefill_programs={b_programs}")
    emit("serve_continuous", c_wall * 1e6,
         f"toks_per_s={c_tps:.1f};occupancy={sched.mean_occupancy():.2f};"
         f"decode_programs={sched.compiled_decode_programs()};"
         f"speedup_x={c_tps / b_tps:.2f}")
    if args.chunked_prefill:
        k_results, k_wall, k_toks, ksched = run_continuous(
            params, cfg, reqs, arrivals, args.max_len, args.slots, mesh=mesh,
            chunked=True,
        )
        # Chunked prefill must not change a single greedy token.
        for r in k_results:
            np.testing.assert_array_equal(ref[r.uid], r.tokens)
        l_p50, l_p95 = ttft_stats(c_results)
        k_p50, k_p95 = ttft_stats(k_results)
        chunk_sizes = ksched.policy.chunk_sizes
        emit("serve_prefill", c_wall * 1e6,
             f"mode=legacy;ttft_p50_ms={l_p50:.2f};ttft_p95_ms={l_p95:.2f};"
             f"prefill_programs={sched.compiled_prefill_programs()}")
        emit("serve_prefill", k_wall * 1e6,
             f"mode=chunked;ttft_p50_ms={k_p50:.2f};ttft_p95_ms={k_p95:.2f};"
             f"prefill_programs={ksched.compiled_prefill_programs()};"
             f"admit_programs={ksched.compiled_admit_programs()};"
             f"chunk_sizes={'/'.join(map(str, chunk_sizes))};"
             f"toks_per_s={k_toks / k_wall:.1f}")
        snapshots["chunked"] = ksched.obs.registry.snapshot()
        if args.smoke:
            # bounded compile set: independent of the length mix (the
            # workload has one distinct length per request)
            assert ksched.compiled_prefill_programs() <= len(chunk_sizes) + 1, (
                ksched.compiled_prefill_programs(), chunk_sizes)
            assert ksched.compiled_admit_programs() == 1
            assert ksched.compiled_decode_programs() == 1
            assert sched.compiled_prefill_programs() >= len(
                {len(r.tokens) for r in reqs()})
    if args.paged:
        bs = args.block_size
        n_blocks = paged_pool_size(reqs(), args.slots, bs)
        if mesh is not None:
            # round the pool up to the data-axis size so the block axis
            # (and with it the block tables) can shard evenly
            d_ax = dict(mesh.shape).get("data", 1)
            n_blocks = -(-n_blocks // d_ax) * d_ax
        p_results, p_wall, p_toks, psched = run_continuous(
            params, cfg, reqs, arrivals, args.max_len, args.slots, mesh=mesh,
            paged=True, block_size=bs, n_blocks=n_blocks,
        )
        # Paging must not change a single greedy token.
        for r in p_results:
            np.testing.assert_array_equal(ref[r.uid], r.tokens)
        paged_bytes = cache_bytes(psched.pool)
        unpaged_bytes = cache_bytes(sched.pool)
        alloc = psched.pool.allocator
        leaked = alloc.n_blocks - alloc.free_count
        p_tpot50, p_tpot95 = tpot_stats(psched)
        gather_read = attn_read_bytes_per_step(cfg, psched, kernel=False)
        emit("serve_paged_hbm", p_wall * 1e6,
             f"block_size={bs};n_blocks={n_blocks};"
             f"cache_bytes={paged_bytes};unpaged_cache_bytes={unpaged_bytes};"
             f"shrink_x={unpaged_bytes / max(paged_bytes, 1):.2f};"
             f"block_occupancy={psched.mean_block_occupancy():.2f};"
             f"fragmentation={psched.mean_fragmentation():.2f};"
             f"leaked_blocks={leaked};toks_per_s={p_toks / p_wall:.1f};"
             f"tpot_p50_ms={p_tpot50:.2f};tpot_p95_ms={p_tpot95:.2f};"
             f"attn_read_bytes_per_step={gather_read}")
        snapshots["paged"] = psched.obs.registry.snapshot()
        if args.smoke:
            assert leaked == 0, f"{leaked} blocks leaked"
            assert alloc.committed == 0, alloc.committed
            assert psched.compiled_decode_programs() == 1
            # cache memory must scale with live tokens, not slots*max_len
            assert unpaged_bytes > 1.5 * paged_bytes, (unpaged_bytes, paged_bytes)
        if args.paged_kernel:
            pk_results, pk_wall, pk_toks, pksched = run_continuous(
                params, cfg, reqs, arrivals, args.max_len, args.slots, mesh=mesh,
                paged=True, block_size=bs, n_blocks=n_blocks, paged_kernel=True,
            )
            # The kernel must not change a single greedy token either.
            for r in pk_results:
                np.testing.assert_array_equal(ref[r.uid], r.tokens)
            k_alloc = pksched.pool.allocator
            k_leaked = k_alloc.n_blocks - k_alloc.free_count
            k_tpot50, k_tpot95 = tpot_stats(pksched)
            kernel_read = attn_read_bytes_per_step(cfg, pksched, kernel=True)
            read_ratio = gather_read / max(kernel_read, 1)
            emit("serve_paged_kernel", pk_wall * 1e6,
                 f"block_size={bs};n_blocks={n_blocks};"
                 f"table_shards={pksched.pool.table_shards};"
                 f"leaked_blocks={k_leaked};toks_per_s={pk_toks / pk_wall:.1f};"
                 f"tpot_p50_ms={k_tpot50:.2f};tpot_p95_ms={k_tpot95:.2f};"
                 f"attn_read_bytes_per_step={kernel_read};"
                 f"gather_read_bytes_per_step={gather_read};"
                 f"read_shrink_x={read_ratio:.2f}")
            snapshots["paged_kernel"] = pksched.obs.registry.snapshot()
            if args.smoke:
                assert k_leaked == 0, f"{k_leaked} blocks leaked"
                assert pksched.compiled_decode_programs() == 1
                # per-step attention HBM reads must scale with live
                # tokens, not pool capacity
                assert read_ratio >= 2.0, (kernel_read, gather_read)
                if mesh is not None:
                    # block tables co-shard with the pool over the data axis
                    d_ax = dict(mesh.shape).get("data", 1)
                    assert pksched.pool.table_shards == d_ax, (
                        pksched.pool.table_shards, d_ax)
        if args.overload:
            rates = tuple(args.arrival_rate * m
                          for m in ((0.5, 2.0, 8.0) if args.smoke
                                    else (0.5, 1.0, 2.0, 4.0, 8.0)))
            osched, overload_stats = run_overload(
                params, cfg, reqs, ref, args.max_len, args.slots,
                args.block_size, rates, arrival_seed=0, smoke=args.smoke)
            snapshots["overload"] = osched.obs.registry.snapshot()
            if args.degrade:
                dsched, degrade_stats = run_degrade(
                    params, cfg, reqs, args.max_len, args.slots,
                    args.block_size, rates, arrival_seed=0,
                    baseline_stats=overload_stats, smoke=args.smoke)
                snapshots["degrade"] = dsched.obs.registry.snapshot()
        if args.spec_decode:
            from repro.serve import ServeEngine

            p_tps = p_toks / p_wall
            # dp == n_bits is the degenerate-but-legal top point: drafts
            # are bitwise-exact (acceptance 1.0), isolating the fused
            # round's dispatch amortisation from the precision tradeoff.
            sweep = tuple(dp for dp in (1, 2, 3) if dp <= args.packed_bits)
            s_engine = ServeEngine(
                params, cfg, max_len=args.max_len, continuous=True,
                n_slots=args.slots, mesh=mesh, paged=True, block_size=bs,
                n_blocks=n_blocks, spec_decode=True,
                draft_planes=sweep[0], gamma=args.gamma)
            ssched = s_engine.scheduler
            for dp in sweep:
                # The draft depth is a RUNTIME operand into the fused
                # draft+verify program: the whole sweep reuses one
                # engine and compiles nothing new between points.
                ssched.policy.draft_planes = dp
                s_engine.generate(reqs(), arrival_steps=arrivals)  # warmup
                ssched.pool.reset()
                ssched.reset_telemetry()
                t0 = time.perf_counter()
                s_results = s_engine.generate(reqs(), arrival_steps=arrivals)
                s_wall = time.perf_counter() - t0
                # Speculation must never change a greedy token.
                for r in s_results:
                    np.testing.assert_array_equal(ref[r.uid], r.tokens)
                s_alloc = ssched.pool.allocator
                s_leaked = s_alloc.n_blocks - s_alloc.free_count
                s_toks = sum(len(r.tokens) for r in s_results)
                s_tps = s_toks / s_wall
                accept = ssched.spec_accept_rate()
                emit("serve_spec", s_wall * 1e6,
                     f"draft_planes={dp};gamma={args.gamma};"
                     f"accept_rate={accept:.3f};rounds={ssched.spec_rounds};"
                     f"drafted={ssched.spec_drafted};"
                     f"committed={ssched.spec_committed};"
                     f"toks_per_s={s_tps:.1f};"
                     f"speedup_x={s_tps / p_tps:.2f};"
                     f"spec_programs={ssched.compiled_spec_programs()};"
                     f"leaked_blocks={s_leaked}")
                spec_stats.append({
                    "draft_planes": dp, "gamma": args.gamma,
                    "accept_rate": accept, "rounds": ssched.spec_rounds,
                    "drafted": ssched.spec_drafted,
                    "committed": ssched.spec_committed,
                    "toks_per_s": s_tps, "speedup_x": s_tps / p_tps,
                })
                if args.smoke:
                    assert s_leaked == 0, f"{s_leaked} blocks leaked"
                    assert s_alloc.committed == 0, s_alloc.committed
                    assert ssched.spec_rounds > 0
            snapshots["spec"] = ssched.obs.registry.snapshot()
            if args.smoke:
                # one fused program per round depth — NOT per (depth x
                # precision); the sweep would have tripled this if the
                # plane count were compiled in
                assert ssched.compiled_spec_programs() <= args.gamma, (
                    ssched.compiled_spec_programs(), args.gamma)
                best = max(s["speedup_x"] for s in spec_stats)
                assert best >= 1.2, (
                    f"spec decode best speedup {best:.2f}x < 1.2x over the "
                    f"non-speculative paged run ({p_tps:.1f} tok/s)")
    if args.packed_bits:
        glob, per_dev = packed_hbm_stats(sched.engine)
        shrink = glob / max(per_dev, 1)
        emit("serve_packed_hbm", c_wall * 1e6,
             f"bits={args.packed_bits};global_bytes={glob};"
             f"per_dev_bytes={per_dev};shrink_x={shrink:.2f}")
        if mesh is not None and shrink <= n_dev * 0.75:
            # per-device packed HBM should drop by ~the mesh factor (the
            # scale rows are tiny; planes/sign dominate) — hard-fail only
            # under --smoke (CI), warn on exploratory mesh shapes whose
            # dims legitimately don't divide
            msg = (f"packed HBM shrink {shrink:.2f}x < mesh factor {n_dev} "
                   f"(global={glob}, per_dev={per_dev})")
            if args.smoke:
                raise AssertionError(msg)
            print(f"WARNING: {msg}", file=sys.stderr)
        # Quantization-quality probe: the packed model at k active
        # bit-planes vs full precision (logit MSE + greedy top-1
        # agreement).  Gauges land in the continuous engine's registry,
        # so they ride the same Prometheus/JSON export as the serving
        # metrics; rows also land in the --json document.
        from repro.obs.quality import quality_probe

        probe_toks = reqs()[-1].tokens[None, :]  # the workload's longest prompt
        quality_rows = [
            r.to_dict()
            for r in quality_probe(params, cfg, probe_toks,
                                   registry=sched.obs.registry)
        ]
        for q in quality_rows:
            emit("serve_quality", 0.0,
                 f"planes={q['planes']};group={q['group']};"
                 f"logit_mse={q['logit_mse']:.3e};"
                 f"top1_agreement={q['top1_agreement']:.4f}")
        if args.smoke:
            from repro.obs.export import to_prometheus

            full = max(q["planes"] for q in quality_rows)
            by_k = {q["planes"]: q for q in quality_rows}
            assert by_k[full]["top1_agreement"] == 1.0, by_k[full]
            assert by_k[full]["logit_mse"] == 0.0, by_k[full]
            assert by_k[1]["logit_mse"] >= by_k[full]["logit_mse"]
            assert "serve_quality_top1" in to_prometheus(sched.obs.registry)
    if args.json:
        import json

        from benchmarks.common import ROWS

        snapshots["continuous"] = sched.obs.registry.snapshot()
        doc = {
            # Bump schema_version on any breaking change to this layout;
            # consumers (CI artifact readers) key on it.
            "schema_version": 1,
            "workload": {
                "arch": args.arch, "requests": args.requests,
                "max_new": args.max_new, "max_len": args.max_len,
                "slots": args.slots, "arrival_rate": args.arrival_rate,
                "packed_bits": args.packed_bits,
            },
            "rows": [
                dict(zip(("name", "us_per_call", "derived"), r.split(",", 2)))
                for r in ROWS
            ],
            "metrics": snapshots,
            "quality": quality_rows,
            # Additive (schema_version stays 1): per-rate overload sweep
            # stats, one object per offered rate, empty without --overload.
            "overload": overload_stats,
            # Additive: the spec-decode draft_planes sweep, one object per
            # draft depth (acceptance rate + speedup vs the non-spec paged
            # run), empty without --spec-decode.
            "spec": spec_stats,
            # Additive: the degrade sweep, one object per offered rate
            # (shed/restore counters + goodput vs the request-shedding
            # overload baseline), empty without --degrade.  See
            # BENCH_JSON_KEYS for the additive-key versioning policy.
            "degrade": degrade_stats,
        }
        validate_bench_json(doc)  # the artifact CI consumes must parse
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
    if args.smoke:
        assert sched.compiled_decode_programs() == 1, "must be ONE decode program"
        assert c_toks == b_toks
        print("SMOKE_OK", flush=True)
    elif c_tps <= b_tps:
        print(f"WARNING: continuous ({c_tps:.1f} t/s) did not beat "
              f"bucketed ({b_tps:.1f} t/s) on this workload", file=sys.stderr)


if __name__ == "__main__":
    # allow `python benchmarks/bench_serve.py` from an uninstalled checkout
    _root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(_root, "src"))
    sys.path.insert(0, _root)
    main()
