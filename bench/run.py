#!/usr/bin/env python3
"""Chip benchmark of packed, paged serving: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a ``workloads`` entry of ``BENCHMARK.json``; it names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  Per-layer metrics are read by
``bench/metrics/<metric>.py``.  The run

1. draws the weights from the seed in their served form, builds
   ``ServeEngine(continuous=True, paged=True, paged_kernel=True)`` and
   runs every program the window can use once (set-up);
2. streams the mix's closed-loop requests through ``engine.stream`` and
   measures ``--seconds`` from the moment the mix's window opens;
3. with ``--trace 1``, records a device trace of that window and prints
   the per-layer metrics instead of the end-to-end ones;
4. frees the program's state and compares a sample of the finished
   requests with the configuration's plain reference (``bench/check.py``).

The last line of standard output is one JSON object.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.  JAX's compilation cache is kept in
``<checkout>/.jax_cache``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
FLIGHT_CAPACITY = 1 << 20  # request traces kept: every request of a run


class BenchError(Exception):
    """A run that cannot measure: printed on standard error, exit 1."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"{path.relative_to(ROOT)} is missing")
    return json.loads(path.read_text())


def load_cell(name: str):
    """(benchmark, workload, configuration entry, configuration file, mix)."""
    bench = read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = read_json(ROOT / entry["file"])
    mix = read_json(ROOT / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, entry, config, mix


def cell_metrics(bench: dict, cell: str):
    """The end-to-end and per-layer metric entries this cell reports."""
    e2e = [m for m in bench["end_to_end"] if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if cell in m.get("workloads", [cell]) and m["moves"] in names]
    return e2e, layer


def load_reader(metric: str):
    path = ROOT / "bench" / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise BenchError(f"no reader for per-layer metric {metric!r} at {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def counters(engine) -> dict:
    """Program counters read at each end of the window."""
    from bench import hostspans

    reg = engine.obs.registry
    occ = reg.histogram("serve_occupancy")
    attn = reg.histogram("serve_attn_read_blocks")
    ptok = reg.counter("serve_prefill_tokens_total", labels=("path",))
    sched = engine.scheduler
    return {"t": time.perf_counter(), "occ_count": occ.count, "occ_sum": occ.sum,
            "attn_blocks": attn.sum,
            "decode_steps": sched.decode_steps, "prefill_chunks": sched.prefill_chunks,
            "prefill_tokens": {p: ptok.labels(path=p).value for p in ("decode", "chunk")},
            **hostspans.counters(reg)}


def process_age() -> float:
    """Seconds this process had run when ``T_START`` was taken (from
    /proc, to the kernel's clock tick; 0 where /proc is absent)."""
    try:
        start_ticks = int(open("/proc/self/stat").read().rsplit(")", 1)[1].split()[19])
        uptime = float(open("/proc/uptime").read().split()[0])
    except (OSError, ValueError, IndexError):
        return 0.0
    return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK")
               - (time.perf_counter() - T_START))


def start_jax():
    """Import JAX with its persistent compilation cache in one fixed
    directory inside the checkout, for the benchmark and the program
    alike, holding every program however small.  The cache never evicts:
    an evicting cache takes a file lock on every read and write, which
    set-up's parallel compiles time out on (entries then go unwritten)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        raise BenchError("--seed must be >= 0 and --seconds > 0")

    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"the system under test is not at {ROOT / 'src'}")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    bench, cell, _, config, mix = load_cell(args.workload)
    e2e_specs, layer_specs = cell_metrics(bench, args.workload)
    readers = {m["name"]: load_reader(m["name"]) for m in layer_specs} if args.trace else {}

    jax = start_jax()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise BenchError(f"JAX finds no TPU (device 0 is {dev.platform}); "
                         "this benchmark never runs elsewhere")
    if len(devices) < cell["chips"]:
        raise BenchError(f"cell needs {cell['chips']} chips, JAX finds {len(devices)}")

    from bench import peaks

    peak = peaks.peaks(dev.device_kind)
    result, lines = run_cell(args.workload, args.seed, args.seconds, args.trace, config, mix,
                             e2e_specs, layer_specs, readers, dev, len(devices), peak)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


class WindowWatch:
    """What happens on the host while armed (the window): programs lowered
    or compiled, and the time Python's garbage collector holds the
    process."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        import jax

        self.n, self.armed, self.names = 0, False, []
        self.gc_s, self.gc_full, self._gc_t = 0.0, 0, None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def _on_event(self, event, duration, fun_name=None, **kw):
        if self.armed and event in self.EVENTS:
            self.n += 1
            self.names.append(fun_name)

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_t = time.perf_counter()
        elif self._gc_t is not None:
            if self.armed:
                self.gc_s += time.perf_counter() - self._gc_t
                self.gc_full += info["generation"] == 2
            self._gc_t = None


def serve_window(engine, mix: dict, seed: int, seconds: float, vocab: int,
                 watch: WindowWatch, trace_dir=None, age: float = 0.0) -> dict:
    """Stream the mix's requests for ``seed`` through ``engine`` until the
    window closes; with ``trace_dir``, trace the window there.  Returns
    the window, the request events, the finished Results and set-up."""
    import jax

    from bench import devtrace, serve, traffic

    requests = traffic.generate(mix, seed, vocab)
    n_slots = mix["clients"]
    sched = engine.scheduler
    # The window opens at the clients' n-th first token: the lanes' first
    # prefill is over and refills have begun.
    marks = {"first": 0}

    def opens(kind: str) -> bool:
        marks["first"] += kind == "first_token"
        return marks["first"] >= n_slots

    def on_open() -> dict:
        watch.armed = True
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # Python calls would slow the host
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(devtrace.OPEN_MARK):
                pass
        c = counters(engine)
        marks["setup_s"] = c["t"] - T_START + age
        return c

    def on_close() -> dict:
        watch.armed = False
        c = counters(engine)
        if trace_dir:
            with jax.profiler.TraceAnnotation(devtrace.CLOSE_MARK):
                pass
            jax.profiler.stop_trace()
        return c

    engine.obs.recorder.clear()
    window = serve.Window(seconds=seconds, opens=opens, on_open=on_open, on_close=on_close)
    n_before, gc_before = watch.n, (watch.gc_s, watch.gc_full)
    finished = serve.run_window(engine, serve.program_requests(requests), window)
    window.opens = window.on_open = window.on_close = None  # they hold the engine
    # Set-up runs every program the current serving path can reach; a
    # compile here means the program reached a shape set-up did not.
    log(f"programs compiled or loaded inside the window: {watch.n - n_before} "
        f"{sorted(set(watch.names[n_before:]))}")
    log(f"garbage collection inside the window: {watch.gc_full - gc_before[1]} full "
        f"collections, {watch.gc_s - gc_before[0]:.4f} s in all")
    events = serve.events_by_request(engine.obs.recorder)
    max_new = {r.uid: r.max_new for r in requests}
    bad = [r.uid for r in finished if len(r.tokens) != max_new[r.uid]
           or r.tokens.min() < 0 or r.tokens.max() >= vocab]
    return {"window": window, "events": {u: e for u, e in events.items() if u >= 0},
            "finished": {r.uid: r.tokens for r in finished}, "bad": bad,
            "prompts": {r.uid: r.prompt for r in requests}, "setup_s": marks["setup_s"]}


def run_cell(name: str, seed: int, seconds: float, trace: int, config: dict, mix: dict,
             e2e_specs: list, layer_specs: list, readers: dict, dev, chips: int, peak: dict):
    """Set-up, window, metrics and comparison of one run; returns the
    result object and the check lines that end standard error."""
    from bench import check, devtrace, hostspans, measure, traffic

    age = process_age()
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": chips}
    log(f"cell {name} seed {seed} on {chips} x {dev.device_kind}")
    watch = WindowWatch()
    engine, params, t_weights = build(config, mix, seed, dev)
    log(f"weights drawn in {t_weights:.4f} s; "
        f"peak bytes in use {(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    vocab = config["model"]["vocab"]
    run = serve_window(engine, mix, seed, seconds, vocab, watch,
                       trace_dir=tmp.name if tmp else None, age=age)
    device["memory_peak_bytes"] = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    window, events = run["window"], run["events"]
    t_open, t_close = window.t_open, window.t_close
    e2e = measure.end_to_end(events, t_open, t_close)
    e2e["setup_s"] = run["setup_s"]
    log(f"window {t_close - t_open:.6f} s: {e2e['n_gaps']} token gaps, tpot p50 "
        f"{e2e['tpot_p50_ms']} ms; {e2e['n_ttft']} first tokens, ttft p50 {e2e['ttft_p50_ms']} ms; "
        f"{len(run['finished'])} requests finished; decode steps "
        f"{window.at_close['decode_steps'] - window.at_open['decode_steps']}, chunk dispatches "
        f"{window.at_close['prefill_chunks'] - window.at_open['prefill_chunks']}; "
        f"setup {run['setup_s']:.3f} s")
    thirds = [t_open + i * (t_close - t_open) / 3 for i in range(4)]
    stalls = measure.stalls(events, t_open, t_close)
    log(f"tokens in each third of the window: "
        f"{[measure.tokens_in_window(events, a, b) for a, b in zip(thirds, thirds[1:])]}; "
        f"{stalls['steps']} steps, median {stalls['median_ms']} ms; {stalls['n']} over "
        f"1.5 x the median, {stalls['excess_s']} s beyond it")
    result = {"correct": None, "attempted": measure.attempted(events, t_open, t_close),
              "failed": len(run["bad"])}

    metrics = {}
    if trace:
        dev_events, host_events = devtrace.split(devtrace.load(tmp.name))
        tmp.cleanup()
        reduced = devtrace.reduce_events(dev_events, host_events)
        spans = hostspans.reduce(dev_events, host_events)
        del dev_events, host_events
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        ref = check.reference(config)
        ctx = {"config": config, "model": config["model"], "reference": ref,
               "dims": ref.dims_of(config["model"]),
               "n_bits": config["n_bits"], "n_slots": mix["clients"], "peak": peak,
               "block_size": engine.scheduler.pool.block_size, "init_weights_s": t_weights,
               "at_open": window.at_open, "at_close": window.at_close,
               "t_open": t_open, "t_close": t_close, "events": events,
               "prompt_len": {u: len(p) for u, p in run["prompts"].items()}, "trace": reduced,
               "host_spans": spans}
        for m in layer_specs:
            v = readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = reduced["breakdown"]
        for line in reduced["notes"] + spans["notes"]:
            log(line)
    else:
        for m in e2e_specs:
            v = e2e.get(m["name"])
            if v is None:
                raise BenchError(f"no {m['name']} was measured in the window")
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # The reference runs once the program's state is gone.
    del engine, params
    gc.unfreeze()
    gc.collect()
    t_ref = time.perf_counter()
    read = check.served_gaps(config, seed, run["prompts"], run["finished"], traffic.max_len(mix))
    limit = config["limits"]["max_logit_gap"]
    log(f"reference over {read['requests_compared']} requests, {read['tokens_compared']} "
        f"served tokens in {time.perf_counter() - t_ref:.1f} s")
    result["correct"] = bool(limit is not None and read["tokens_compared"] > 0
                             and not run["bad"] and read["max_logit_gap"] <= limit)
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {
        "max_logit_gap": {"value": read["max_logit_gap"], "limit": limit, "holds": "<="},
        "tokens_compared": {"value": read["tokens_compared"], "limit": 1, "holds": ">="},
        "bad_requests": {"value": len(run["bad"]), "limit": 0, "holds": "<="},
    }
    lines = [f"check {k} {c['value']} {c['holds']} limit {c['limit']}"
             for k, c in result["checks"].items()]
    return result, lines


def program_config(config: dict):
    """The program's ``ModelConfig``: the registry's ``program.arch`` with
    ``program.overrides`` (a JSON list, such as a layer pattern, becomes
    the tuple the program hashes)."""
    from repro.configs import get_config

    prog = config["program"]
    return get_config(prog["arch"]).scaled(
        **{k: tuple(v) if isinstance(v, list) else v for k, v in prog["overrides"].items()})


def build(config: dict, mix: dict, seed: int, dev):
    """Weights, engine and warm-up: everything before the window's stream."""
    import jax

    from bench import check, serve, traffic, weights
    from repro.core.packing import PackedWeight
    from repro.models import init_packed_params
    from repro.obs import Observability
    from repro.serve import ServeEngine

    cfg = program_config(config)
    ref = check.reference(config)
    differ = ref.program_differs(config["model"], cfg)
    if differ:
        raise BenchError(f"configuration and program disagree (file, program): {differ}")
    n_bits = config["n_bits"]
    template = jax.eval_shape(lambda: init_packed_params(jax.random.PRNGKey(0), cfg, n_bits))
    t0 = time.perf_counter()
    params = weights.program_params(seed, template, ref, config["model"], n_bits,
                                    cfg.pattern_len, PackedWeight)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t0
    max_len = traffic.max_len(mix)
    # The program's default block; the pool holds every lane's longest
    # request, so admission never waits for blocks.
    block = 32
    engine = ServeEngine(params, cfg, max_len=max_len, continuous=True,
                         n_slots=mix["clients"], paged=True, paged_kernel=True,
                         block_size=block, n_blocks=mix["clients"] * math.ceil(max_len / block),
                         obs=Observability(recorder=serve.make_recorder(FLIGHT_CAPACITY)))
    chunk_sizes = engine.scheduler.policy.chunk_sizes
    warm, arrivals = serve.warm_requests(cfg.vocab_size, chunk_sizes)
    t1 = time.perf_counter()
    for _ in engine.stream(warm, arrivals):
        pass
    k = serve.warm_table_updates(engine.scheduler.pool, max(chunk_sizes))
    # What set-up leaves on the heap (a cold run's compiles leave more)
    # is not scanned again by collections inside the window.
    gc.collect()
    gc.freeze()
    log(f"warm-up: every program and {k} block-table update shapes in "
        f"{time.perf_counter() - t1:.3f} s")
    return engine, params, t_weights


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        log(f"error: {e}")
        sys.exit(1)
