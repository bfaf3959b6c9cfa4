"""The scheduler's host spans as the benchmark reads them: the reduction
of ``serve.*`` spans against the device's idle time, the readers of the
four scheduler metrics, the spans the program really writes into a CPU
profile, and a traced run of the harness that reads them."""
import importlib.util
import pathlib

import jax
import pytest

from bench import devtrace, hostspans, run
from repro.obs import Registry

from test_benchmark_control import MIX, PEAK, SECONDS, TINY

METRICS = pathlib.Path(__file__).resolve().parents[2] / "bench" / "metrics"


def _reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


WINDOW = [("bench_window_open", 0.0, 1.0), ("bench_window_close", 9.0, 9.5)]
# Busy 1-2, 3-4 and 6-8 of the window 1-9: idle 2-3, 4-6 and 8-9.
DEVICE = {"XLA Ops": [("%fusion.1 = f32[4]{0} fusion()", 0.5, 2.0),
                      ("%fusion.2 = f32[4]{0} fusion()", 3.0, 4.0),
                      ("%fusion.3 = f32[4]{0} fusion()", 6.0, 8.0)]}
HOST = WINDOW + [
    ("serve.step#step=0,decoding=4#", 0.5, 4.5),  # straddles the open
    ("serve.prefill", 2.0, 2.6), ("serve.blocks", 2.1, 2.3), ("PjitFunction(less)", 2.1, 2.2),
    ("serve.decode", 2.6, 3.5), ("serve.sync", 2.8, 3.2), ("serve.finish", 3.5, 3.7),
    ("serve.step#step=1#", 5.0, 8.5),
    ("serve.admit", 5.0, 5.4), ("serve.decode", 5.4, 8.2), ("serve.sync", 5.6, 6.5),
]


def test_idle_is_split_by_the_innermost_span():
    r = hostspans.reduce(DEVICE, HOST)
    assert r["window_s"] == pytest.approx(8.0)
    assert r["steps"] == 2
    assert r["idle_s"] == pytest.approx(4.0)
    want = {"prefill": 0.4, "blocks": 0.2, "decode": 0.6, "sync": 0.6, "admit": 0.4,
            "step": 0.8, hostspans.OUTSIDE: 1.0}
    assert r["idle_by_span"] == pytest.approx(want)
    # inside a step and outside its syncs: 4.0 - 1.0 outside - 0.6 sync
    assert r["idle_host_s"] == pytest.approx(2.4)
    assert r["notes"][0].startswith("host spans: 2 steps")


def test_span_arguments_are_stripped_from_names():
    named = hostspans.spans(HOST, 1.0, 9.0)
    assert named[hostspans.STEP] == [(1.0, 4.5), (5.0, 8.5)]
    assert "PjitFunction(less)" not in named


def test_a_trace_without_steps_reads_no_host_idle():
    r = hostspans.reduce(DEVICE, WINDOW + [("PjitFunction(add)", 2.0, 3.0)])
    assert r["steps"] == 0 and r["idle_host_s"] == 0.0
    assert r["idle_by_span"] == pytest.approx({hostspans.OUTSIDE: 4.0})


def _ends(count, ms, sync_s, blocks_s, via_decode=0, via_chunk=0):
    return {"step_count": count, "step_ms_sum": ms,
            "host_s": {p: {"sync": sync_s, "blocks": blocks_s}.get(p, 0.0)
                       for p in hostspans.PHASES},
            "prefill_tokens": {"decode": via_decode, "chunk": via_chunk}}


def test_readers_give_their_values():
    ctx = {"at_open": _ends(10, 1000.0, 0.5, 0.10, 40, 100),
           "at_close": _ends(20, 2600.0, 1.4, 0.13, 100, 140),
           "host_spans": hostspans.reduce(DEVICE, HOST)}
    assert _reader("host_step_ms")(ctx) == pytest.approx((1600.0 - 900.0) / 10)
    assert _reader("block_table_ms")(ctx) == pytest.approx(3.0)
    assert _reader("idle_host_share")(ctx) == pytest.approx(30.0)
    assert _reader("prefill_via_decode_share")(ctx) == pytest.approx(60.0)


@pytest.mark.parametrize("name", ["host_step_ms", "block_table_ms", "idle_host_share",
                                  "prefill_via_decode_share"])
def test_readers_read_none_without_steps(name):
    """A program that records no ``serve.step`` and prefills nothing (zero
    counts, no spans in the trace), and a harness whose context lacks the
    readings."""
    still = {"at_open": _ends(7, 70.0, 0.0, 0.0, 5, 5), "at_close": _ends(7, 70.0, 0.0, 0.0, 5, 5),
             "host_spans": hostspans.reduce(DEVICE, WINDOW)}
    assert _reader(name)(still) is None
    bare = {"at_open": {"occ_count": 1}, "at_close": {"occ_count": 2}}
    assert _reader(name)(bare) is None


def test_counters_read_zero_before_any_span():
    c = hostspans.counters(Registry())
    assert c["step_count"] == 0 and c["step_ms_sum"] == 0.0
    assert c["host_s"] == {p: 0.0 for p in hostspans.PHASES}


def test_the_program_writes_the_spans_the_reduction_reads(tmp_path):
    """A traced window of the tiny serving run on the CPU: its host plane
    holds the program's steps and phases, matched by the reduction, and
    the registry holds the same phases."""
    watch = run.WindowWatch()
    engine, _, _ = run.build(TINY, MIX, 3, jax.devices("cpu")[0])
    before = hostspans.counters(engine.obs.registry)
    run.serve_window(engine, MIX, 3, SECONDS / 2, TINY["model"]["vocab"], watch,
                     trace_dir=str(tmp_path))
    after = hostspans.counters(engine.obs.registry)
    assert after["step_count"] > before["step_count"]
    assert after["host_s"]["blocks"] > before["host_s"]["blocks"]
    host = [ev for plane in devtrace.load(str(tmp_path)).planes
            if plane.name.startswith("/host:CPU") for ln in plane.lines
            for ev in devtrace._events(ln)]
    r = hostspans.reduce({"XLA Ops": []}, host)  # no device ops: all idle
    assert r["steps"] > 0
    assert r["idle_s"] == pytest.approx(r["window_s"])
    assert sum(r["idle_by_span"].values()) == pytest.approx(r["idle_s"])
    assert {"admit", "blocks", "prefill", "decode", "sync", "finish"} <= set(r["idle_by_span"])
    assert 0 < r["idle_host_s"] < r["idle_s"]


def test_a_traced_run_reads_the_scheduler_metrics(monkeypatch):
    """``run_cell`` with ``--trace 1`` on the CPU: the trace is split once
    (the CPU has no device plane, so its device half is given empty) and
    both halves feed the device reduction and the host spans; the
    window's counters feed the four scheduler readers, and ``step_mfu``
    reads the reference's FLOPs.  ``correct`` means what it means
    untraced."""
    def cpu_split(profile):
        host = [ev for plane in profile.planes if plane.name.startswith("/host:CPU")
                for ln in plane.lines for ev in devtrace._events(ln)]
        return {"XLA Ops": [], "XLA Modules": []}, host

    monkeypatch.setattr(devtrace, "split", cpu_split)
    names = ["host_step_ms", "block_table_ms", "idle_host_share", "prefill_via_decode_share",
             "decode_occupancy", "step_mfu", "decode_step_ms"]
    specs = [{"name": n, "unit": "%"} for n in names]
    readers = {n: run.load_reader(n) for n in names}
    result, lines = run.run_cell("tiny", 2**31 + 17, SECONDS, 1, TINY, MIX, [], specs, readers,
                                 jax.devices("cpu")[0], 1, PEAK)
    assert result["correct"] is True, lines
    got = result["metrics"]
    # no device program ran in the trace: nothing for the device readers
    assert "decode_step_ms" not in got
    assert set(got) == set(names) - {"decode_step_ms"}
    assert got["host_step_ms"]["value"] > got["block_table_ms"]["value"] > 0
    assert 0 < got["idle_host_share"]["value"] < 100
    assert 0 < got["prefill_via_decode_share"]["value"] <= 100
    assert result["device"]["busy_s"] == 0 and result["device"]["window_s"] > 0
