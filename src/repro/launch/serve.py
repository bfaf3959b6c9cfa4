"""Serving launcher: load (or train-and-quantise) a model, serve requests.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b \
        --requests 8 --max-new 32 [--full] [--packed-bits 6] \
        [--data-parallel N --model-parallel M] \
        [--continuous --slots 8 --arrival-rate 0.5 --mixed-lens]

The model is the reduced smoke-size variant of ``--arch`` unless
``--full`` asks for the published widths; weights are random from a
fixed seed.  With ``--packed-bits`` they are drawn and packed one
superblock at a time (``models.init_packed_params``), so a published
width never holds its float model on the device.

Scheduling modes:

* default (bucketed): offline batching — requests grouped by prompt
  length, one compiled program per (length, batch) bucket.  Arrival
  times are ignored; every request must be present up front.
* ``--continuous``: the slot-pool scheduler (repro.serve.scheduler).
  ``--slots N`` persistent decode lanes are allocated once; requests are
  admitted FIFO into free lanes as they arrive and evicted lanes are
  refilled mid-flight, so mixed prompt lengths and staggered arrivals
  share one compiled decode program.  ``--arrival-rate R`` simulates a
  Poisson request stream (mean R arrivals per decode step, seeded);
  ``--mixed-lens`` cycles prompt lengths through {1/2, 1, 3/2, 2} x
  --prompt-len to exercise the mixed-length path.
* ``--chunked-prefill`` (with ``--continuous``): admission fuses into one
  multi-admit dispatch and prompts stream through the pooled program in
  fixed-size chunks, interleaved with decode steps — the prefill
  compiled set is bounded by the chunk-size table instead of growing
  with the number of distinct prompt lengths, and a long prompt no
  longer stalls live decode lanes.
* ``--paged`` (with ``--continuous``; implies chunked prefill): paged KV
  — attention caches become a global pool of ``--blocks`` fixed-size
  ``--block-size``-row blocks plus per-lane block tables, allocated
  on-demand as prompts/decodes grow and freed at eviction, so cache HBM
  scales with live tokens instead of ``--slots * --max-len``.
* ``--paged-kernel`` (with ``--paged``): decode attention runs the
  Pallas block-table-walking kernel (kernels/paged_attention.py) so
  per-step attention HBM reads scale with live tokens instead of the
  pool's logical capacity; without it the decode step gathers each
  lane's full pool view (the conformance reference path).
* ``--overcommit F`` (with ``--paged``): optimistic admission — commit
  up to ``F x`` the pool's physical blocks (most requests finish before
  their worst case); under pressure the scheduler preempts a victim
  lane and re-enqueues it for recompute re-prefill, token-identically.
  ``--tier {throughput,latency,mixed}`` assigns request SLO classes:
  latency-tier requests are admitted first and preempted last (mixed
  marks every 4th request latency).
* ``--spec-decode`` (with ``--paged`` and ``--packed-bits``): bit-plane
  speculative decoding — decode lanes self-draft up to ``--gamma``
  tokens per round running the SAME packed weights at
  ``--draft-planes`` active bit planes (a runtime operand into the
  bitserial matmuls, no second model), then one full-precision
  chunked-prefill verify scores every drafted position in the same
  fused program.  Accepted prefixes commit; rejected tails rewind lane
  positions through the block tables (greedy verify makes the output
  token-identical to non-speculative decode).
* ``--precision-tier {full,economy,mixed}`` (with ``--packed-bits`` and a
  chunked continuous engine): per-request precision classes — economy
  requests decode at ``--economy-planes`` active bit planes through the
  same compiled program (planes is a runtime operand); prefill is always
  full precision.  ``--degrade`` adds load-triggered plane shedding:
  under queue/occupancy/preemption pressure the scheduler sheds one
  plane per pressured step (never below each class's floor) instead of
  shedding requests, restoring after ``--degrade-hysteresis`` calm steps.

With --data-parallel/--model-parallel the engine serves on a real
("data", "model") mesh: params, the KV cache and the slot pool are
sharded under the repro.dist rules (requires N*M local devices, e.g. via
XLA_FLAGS --xla_force_host_platform_device_count).  --packed-bits N
serves bit-plane-packed weights (per-shard PackedWeights on a mesh: the
bitserial matmul runs shard_map'd on local packed bytes; see
docs/packed_format.md).

Observability (docs/observability.md): the engine emits through the
process-global metrics registry and a flight recorder of the last
``--flight-recorder N`` request traces.  ``--metrics-port P`` serves
Prometheus text at ``/metrics`` (P=0 binds an ephemeral port and prints
it); ``--trace-out F`` dumps the recorded spans as JSONL;
``--chrome-trace-out F`` writes a chrome://tracing document.
``--smoke`` self-scrapes once after serving, validates the exposition,
the required metric families and the trace schema, and prints
``OBS_SMOKE_OK`` (the CI wiring).
"""
import argparse
import resource

import jax
import jax.numpy as jnp
import numpy as np

from .cache import setup_compilation_cache


def poisson_arrivals(n: int, rate: float, seed: int = 0):
    """Arrival steps for a simulated Poisson stream: exponential
    inter-arrival gaps with mean 1/rate decode steps, cumulated and
    floored onto the scheduler's integer step clock."""
    if rate <= 0:
        return [0] * n
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(scale=1.0 / rate, size=n)
    return np.floor(np.cumsum(gaps)).astype(int).tolist()


def main(argv=None):
    """Parse ``argv`` (default: the command line), serve, and return
    ``(engine, results)`` so a caller in the same process can inspect
    the compiled programs and outputs."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--full", action="store_true",
                    help="serve the published widths (default: the reduced "
                         "smoke-size variant of --arch)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--data-parallel", type=int, default=0)
    ap.add_argument("--model-parallel", type=int, default=0)
    ap.add_argument("--continuous", action="store_true",
                    help="serve through the slot-pool continuous-batching scheduler")
    ap.add_argument("--slots", type=int, default=8,
                    help="slot-pool lanes (continuous mode)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="stream prompts through the pooled program in "
                         "fixed-size chunks (continuous mode; bounded "
                         "compile set + fused multi-admit)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV: attention caches become a global pool of "
                         "fixed-size blocks + per-lane block tables, so cache "
                         "HBM scales with live tokens instead of "
                         "slots * max-len (continuous mode; implies "
                         "--chunked-prefill)")
    ap.add_argument("--block-size", type=int, default=32,
                    help="rows per KV block (with --paged); align with the "
                         "chunk sizes so chunk boundaries land on block "
                         "boundaries")
    ap.add_argument("--blocks", type=int, default=0,
                    help="total KV blocks in the pool (with --paged); 0 sizes "
                         "it to the unpaged capacity slots * ceil(max-len / "
                         "block-size)")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="decode attention walks the block table in place via "
                         "the Pallas paged-attention kernel instead of "
                         "gathering each lane's full pool view — per-step "
                         "attention HBM reads scale with live tokens (with "
                         "--paged)")
    ap.add_argument("--overcommit", type=float, default=1.0,
                    help="admit against this multiple of the pool's physical "
                         "blocks (with --paged); > 1.0 enables preemption: "
                         "under pressure a victim lane's blocks are reclaimed "
                         "and the request re-prefills prompt + generated "
                         "tokens (token-identical recompute swap)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="bit-plane speculative decoding (with --paged and "
                         "--packed-bits): decode lanes self-draft --gamma "
                         "steps from the --draft-planes most significant bit "
                         "planes of the same packed weights (a runtime "
                         "operand — one compiled program per round depth), "
                         "then one full-precision verify chunk scores every "
                         "drafted position; greedy output is token-identical "
                         "to non-speculative decode")
    ap.add_argument("--draft-planes", type=int, default=2,
                    help="active bit planes during draft steps (with "
                         "--spec-decode); must be < --packed-bits to draft "
                         "cheaper than full precision")
    ap.add_argument("--gamma", type=int, default=4,
                    help="max draft steps per speculative round (with "
                         "--spec-decode); per-lane depth backs off on "
                         "rejections")
    ap.add_argument("--tier", choices=("throughput", "latency", "mixed"),
                    default="throughput",
                    help="SLO class stamped on requests: latency-tier is "
                         "admitted first and preempted last; 'mixed' marks "
                         "every 4th request latency-tier")
    ap.add_argument("--precision-tier", choices=("full", "economy", "mixed"),
                    default="full",
                    help="precision class stamped on requests (with "
                         "--packed-bits and a chunked continuous engine): "
                         "economy-class lanes decode at --economy-planes "
                         "active bit planes through the SAME compiled "
                         "program; 'mixed' marks every other request economy")
    ap.add_argument("--economy-planes", type=int, default=0,
                    help="active bit planes for the economy precision class "
                         "(0 = max(1, --packed-bits // 2)); must be in "
                         "[1, --packed-bits] and above --draft-planes under "
                         "--spec-decode")
    ap.add_argument("--degrade", action="store_true",
                    help="load-triggered plane shedding: when queue depth / "
                         "occupancy / preemption rate cross the policy "
                         "thresholds the engine sheds one active bit plane "
                         "per pressured step (floor-clamped per precision "
                         "class) instead of shedding requests, restoring "
                         "with hysteresis as pressure drops")
    ap.add_argument("--degrade-queue-depth", type=int, default=2,
                    help="queue depth (post-admission) at which the degrade "
                         "loop sheds a plane (with --degrade)")
    ap.add_argument("--degrade-hysteresis", type=int, default=4,
                    help="consecutive calm steps before the degrade loop "
                         "restores a shed plane (with --degrade)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="simulate Poisson arrivals at this mean rate per decode "
                         "step (continuous mode; 0 = all requests at step 0)")
    ap.add_argument("--mixed-lens", action="store_true",
                    help="cycle prompt lengths around --prompt-len")
    ap.add_argument("--packed-bits", type=int, default=0,
                    help="serve bit-plane-packed weights at this precision "
                         "(0 = float); with a mesh the packed bytes shard "
                         "per-device (docs/packed_format.md)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text at /metrics on this port "
                         "(0 = ephemeral, printed at startup; omit to disable)")
    ap.add_argument("--trace-out", default=None,
                    help="dump the flight recorder's request traces as JSONL "
                         "to this path after serving")
    ap.add_argument("--chrome-trace-out", default=None,
                    help="write a chrome://tracing / perfetto document of the "
                         "recorded request spans to this path")
    ap.add_argument("--flight-recorder", type=int, default=256,
                    help="keep the last N completed request traces")
    ap.add_argument("--smoke", action="store_true",
                    help="after serving, scrape the metrics endpoint once, "
                         "validate the exposition + required families + trace "
                         "schema, and print OBS_SMOKE_OK (CI)")
    args = ap.parse_args(argv)
    if args.chunked_prefill and not args.continuous:
        raise SystemExit("--chunked-prefill requires --continuous")
    if args.paged and not args.continuous:
        raise SystemExit("--paged requires --continuous")
    if args.paged_kernel and not args.paged:
        raise SystemExit("--paged-kernel requires --paged")
    if args.overcommit != 1.0 and not args.paged:
        raise SystemExit("--overcommit requires --paged (only the block pool "
                         "has commitment accounting)")
    if args.spec_decode and not args.paged:
        raise SystemExit("--spec-decode requires --paged (draft rollback "
                         "rewinds lane positions through the block tables)")
    if args.spec_decode and not args.packed_bits:
        raise SystemExit("--spec-decode requires --packed-bits (drafting "
                         "truncates the packed weight's bit planes)")
    if args.spec_decode and args.temperature > 0:
        raise SystemExit("--spec-decode requires --temperature 0 (greedy "
                         "verify is what makes spec output token-identical)")
    if args.spec_decode and not 1 <= args.draft_planes < args.packed_bits:
        raise SystemExit(f"--draft-planes {args.draft_planes} must be in "
                         f"[1, --packed-bits {args.packed_bits})")
    tiered = args.precision_tier != "full" or args.degrade
    if tiered and not args.packed_bits:
        raise SystemExit("--precision-tier/--degrade require --packed-bits "
                         "(float weights have no bit planes to shed)")
    if tiered and not (args.chunked_prefill or args.paged):
        raise SystemExit("--precision-tier/--degrade require a chunked "
                         "continuous engine (--continuous with "
                         "--chunked-prefill or --paged)")
    econ_planes = args.economy_planes or max(1, args.packed_bits // 2)
    if args.precision_tier != "full":
        if not 1 <= econ_planes <= args.packed_bits:
            raise SystemExit(f"--economy-planes {econ_planes} must be in "
                             f"[1, --packed-bits {args.packed_bits}]")
        if args.spec_decode and econ_planes <= args.draft_planes:
            raise SystemExit(f"--economy-planes {econ_planes} must exceed "
                             f"--draft-planes {args.draft_planes} (the "
                             "verify must add information over the draft)")

    from ..configs import get_config, reduced_config
    from ..data import MarkovLM
    from ..dist import elastic
    from ..models import init_packed_params, init_params
    from ..serve import Request, ServeEngine
    from .mesh import make_mesh

    setup_compilation_cache()
    cfg = get_config(args.arch) if args.full else reduced_config(args.arch)
    mesh = None
    if bool(args.data_parallel) != bool(args.model_parallel):
        raise SystemExit("--data-parallel and --model-parallel must be given together "
                         "(use 1 for an unsharded axis)")
    if args.data_parallel and args.model_parallel:
        mesh = make_mesh((args.data_parallel, args.model_parallel), ("data", "model"))
        # Advisory only: the engine tolerates indivisible buckets (batch
        # axis replicated), it just loses the data-parallel speedup.
        if not elastic.validate_batch_divisibility(args.requests, mesh):
            print(
                f"[serve] note: --requests {args.requests} does not divide over "
                f"the data axis ({dict(mesh.shape)}); buckets will run with a "
                "replicated batch axis"
            )
    if args.packed_bits:
        from ..core.packing import packed_leaves

        # Drawn and packed superblock by superblock: the float model of a
        # published width never has to fit on the device.
        params = init_packed_params(jax.random.PRNGKey(0), cfg, args.packed_bits)
        packed_bytes = sum(pw.hbm_bytes() for pw in packed_leaves(params))
        print(f"[serve] packed weights at {args.packed_bits}b: "
              f"{packed_bytes / 1e6:.2f} MB global")
    else:
        params = init_params(jax.random.PRNGKey(0), cfg)
    stats = jax.devices()[0].memory_stats()
    if stats and "peak_bytes_in_use" in stats:
        print(f"[serve] device peak bytes in use after init: "
              f"{stats['peak_bytes_in_use']}; host peak RSS "
              f"{resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024}")
    from ..obs import Observability, get_registry

    # Wire the engine to the PROCESS-GLOBAL registry (engines default to a
    # private one) so the scrape endpoint below sees its metrics.
    obs = Observability(registry=get_registry(),
                        flight_capacity=args.flight_recorder)
    server = None
    if args.metrics_port is not None:
        from ..obs.export import start_metrics_server

        server = start_metrics_server(obs.registry, port=args.metrics_port)
        print(f"[obs] metrics at {server.url}")
    engine = ServeEngine(params, cfg, max_len=args.max_len, mesh=mesh,
                         continuous=args.continuous, n_slots=args.slots,
                         chunked_prefill=args.chunked_prefill, paged=args.paged,
                         block_size=args.block_size,
                         n_blocks=args.blocks or None,
                         paged_kernel=args.paged_kernel,
                         overcommit=args.overcommit,
                         spec_decode=args.spec_decode,
                         draft_planes=args.draft_planes, gamma=args.gamma,
                         precision_tiers=({"economy": econ_planes}
                                          if args.precision_tier != "full"
                                          else None),
                         degrade=args.degrade,
                         degrade_queue_depth=args.degrade_queue_depth,
                         degrade_hysteresis=args.degrade_hysteresis,
                         obs=obs)
    task = MarkovLM(vocab=cfg.vocab_size, seed=3)
    if args.mixed_lens:
        lens = [max(2, args.prompt_len * m // 2) for m in (1, 2, 3, 4)]
    else:
        lens = [args.prompt_len]
    def req_tier(i: int) -> str:
        if args.tier == "mixed":
            return "latency" if i % 4 == 0 else "throughput"
        return args.tier

    def req_precision(i: int) -> str:
        if args.precision_tier == "mixed":
            return "economy" if i % 2 else "full"
        return args.precision_tier

    reqs = [
        Request(
            uid=i,
            tokens=task.sample(np.random.default_rng(i), 1, max(lens))[0,
                   : lens[i % len(lens)]].astype(np.int32),
            max_new=args.max_new,
            temperature=args.temperature,
            tier=req_tier(i),
            precision=req_precision(i),
        )
        for i in range(args.requests)
    ]
    arrivals = poisson_arrivals(args.requests, args.arrival_rate) if args.continuous else None
    results = engine.generate(reqs, arrival_steps=arrivals) if args.continuous \
        else engine.generate(reqs)
    for r in sorted(results, key=lambda r: r.uid):
        print(f"req {r.uid}: prefill {r.prefill_ms:.1f} ms, tokens={r.tokens[:8]}...")
    total = sum(len(r.tokens) for r in results)
    print(f"{total} tokens generated")
    if args.continuous:
        sched = engine.scheduler
        print(f"[continuous] slots={args.slots} "
              f"occupancy={sched.mean_occupancy():.2f} "
              f"decode_steps={sched.decode_steps} "
              f"decode_programs={sched.compiled_decode_programs()} "
              f"prefill_programs={sched.compiled_prefill_programs()}")
        if args.chunked_prefill or args.paged:
            print(f"[chunked] chunk_dispatches={sched.prefill_chunks} "
                  f"admit_bursts={len(sched.admit_bursts)} "
                  f"admit_programs={sched.compiled_admit_programs()}")
        if tiered:
            econ = (f"economy={sched.active_planes('economy')}/"
                    f"{econ_planes}" if args.precision_tier != "full"
                    else "economy=-")
            print(f"[tiers] precision_tier={args.precision_tier} "
                  f"full={sched.active_planes('full')}/{args.packed_bits} "
                  f"{econ}")
        if args.degrade:
            print(f"[degrade] sheds={sched.degrade_sheds} "
                  f"restores={sched.degrade_restores} "
                  f"events={sched.degrade_events_total()} "
                  f"queue_depth_trigger={args.degrade_queue_depth} "
                  f"hysteresis={args.degrade_hysteresis}")
        if args.paged:
            pool = sched.pool
            print(f"[paged] block_size={pool.block_size} n_blocks={pool.n_blocks} "
                  f"kernel={args.paged_kernel} table_shards={pool.table_shards} "
                  f"block_occupancy={sched.mean_block_occupancy():.2f} "
                  f"fragmentation={sched.mean_fragmentation():.2f} "
                  f"leaked_blocks={pool.n_blocks - pool.allocator.free_count}")
            if args.overcommit != 1.0:
                print(f"[overcommit] factor={args.overcommit} "
                      f"commit_capacity={pool.allocator.commit_capacity}"
                      f"x{pool.allocator.n_shards} "
                      f"preemptions={sched.preemptions_total()}")
            if args.spec_decode:
                print(f"[spec] draft_planes={args.draft_planes} "
                      f"gamma={args.gamma} rounds={sched.spec_rounds} "
                      f"drafted={sched.spec_drafted} "
                      f"accepted={sched.spec_accepted} "
                      f"committed={sched.spec_committed} "
                      f"accept_rate={sched.spec_accept_rate():.2f} "
                      f"spec_programs={sched.compiled_spec_programs()}")
    if args.trace_out:
        n = obs.recorder.dump_jsonl(args.trace_out)
        print(f"[obs] {n} request traces -> {args.trace_out}")
    if args.chrome_trace_out:
        obs.recorder.dump_chrome_trace(args.chrome_trace_out)
        print(f"[obs] chrome trace -> {args.chrome_trace_out}")
    if args.smoke:
        _obs_smoke(args, obs, server, engine)
    if server is not None:
        server.close()
    return engine, results


def _obs_smoke(args, obs, server, engine):
    """CI self-check: scrape once over HTTP (or render directly when no
    endpoint was requested), validate the exposition parses, the expected
    metric families are populated, no span leaked, and the JSONL trace
    file (if written) passes the schema check.  With ``--degrade`` the
    smoke additionally requires the shed-and-restore cycle to have fired
    (the CI invocation must overload the pool) with zero leaked blocks.
    Prints OBS_SMOKE_OK."""
    from urllib.request import urlopen

    from ..obs import trace as obs_trace
    from ..obs.export import parse_prometheus, to_prometheus

    if server is not None:
        text = urlopen(server.url, timeout=10).read().decode()
    else:
        text = to_prometheus(obs.registry)
    families = parse_prometheus(text)  # raises on any malformed line
    required = ["serve_ttft_ms", "serve_requests_total"]
    if args.continuous:
        required += ["serve_occupancy", "serve_step_ms", "serve_host_seconds_total"]
    if args.paged:
        required += ["serve_blocks_alloc_total", "serve_block_pool_free"]
    if args.spec_decode:
        required += ["serve_spec_rounds_total", "serve_spec_accept_total"]
    if args.precision_tier != "full" or args.degrade:
        required += ["serve_active_planes"]
    if args.degrade:
        required += ["serve_degrade_events_total"]
    missing = [f for f in required
               if f not in families or not families[f]["samples"]]
    if missing:
        raise SystemExit(f"[obs] smoke FAILED: empty/missing families {missing}")
    if obs.recorder.leaked:
        raise SystemExit(f"[obs] smoke FAILED: leaked spans {obs.recorder.leaked}")
    if args.degrade:
        sched = engine.scheduler
        if sched.degrade_sheds < 1 or sched.degrade_restores < 1:
            raise SystemExit(
                f"[obs] smoke FAILED: --degrade ran without a full "
                f"shed-and-restore cycle (sheds={sched.degrade_sheds}, "
                f"restores={sched.degrade_restores}) — overload the pool "
                "(more requests than slots, arrivals at step 0)")
        if args.paged:
            pool = sched.pool
            leaked = pool.n_blocks - pool.allocator.free_count
            if leaked:
                raise SystemExit(f"[obs] smoke FAILED: {leaked} leaked KV "
                                 "blocks after degrade run")
    if args.trace_out:
        n = obs_trace.validate_jsonl(args.trace_out)
        if n < args.requests:
            raise SystemExit(
                f"[obs] smoke FAILED: {n} traces in {args.trace_out} for "
                f"{args.requests} requests")
    print(f"OBS_SMOKE_OK families={len(families)}")


if __name__ == "__main__":
    main()
