"""repro.obs: registry semantics, exposition, span lifecycle, TTFT unity.

Four layers under test:

* metrics — counter/gauge/histogram semantics, bounded-reservoir
  percentile parity with numpy, idempotent registration with
  kind-conflict detection, label cardinality cap, Ring list-equality.
* export — a golden Prometheus text exposition, the parse round-trip,
  malformed-line rejection, and a live ``http.server`` scrape.
* trace — FlightRecorder span lifecycle: double-begin and non-terminal
  finish fail loudly, JSONL dump/validate, chrome://tracing export.
* the TTFT regression: ``Result.prefill_ms`` must equal
  ``RequestTrace.ttft_ms()`` on EVERY serve path (bucketed oracle,
  legacy continuous, chunked, paged) — the one-definition guarantee
  that keeps engine.py and scheduler.py from drifting apart again.

The quality-probe tests pack a tiny model and check the two anchors the
probe is useful for: full planes reproduce full precision exactly
(top-1 == 1.0, MSE == 0), and fewer planes never *improve* logit MSE.
"""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.packing import pack_model_params, packed_leaves, unpack_to_float
from repro.models import init_params
from repro.obs import Observability
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.export import (
    MetricsServer,
    parse_prometheus,
    to_json,
    to_prometheus,
)
from repro.obs.metrics import Histogram, Registry, Ring, percentile
from repro.obs.quality import quality_probe, truncate_packed
from repro.obs.trace import FlightRecorder, validate_jsonl
from repro.serve import Request, SchedulerPolicy, ServeEngine


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_and_gauge_semantics():
    reg = Registry()
    c = reg.counter("c_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match=">= 0"):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5.0


def test_histogram_bounded_reservoir_exact_totals():
    h = Histogram(capacity=4)
    for v in range(10):
        h.observe(v)
    # totals never reset ...
    assert h.count == 10
    assert h.sum == sum(range(10))
    # ... but the reservoir holds only the newest `capacity`, oldest first
    assert h.values() == [6.0, 7.0, 8.0, 9.0]
    assert len(h) == 4
    assert h.last() == 9.0
    assert h.mean() == 7.5
    h.clear()
    assert h.count == 0 and h.values() == []
    assert h.mean() == 0.0 and h.percentile(50) == 0.0 and h.last() is None


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    vals = rng.standard_normal(257).tolist()
    for p in (0, 10, 50, 90, 95, 99, 100):
        assert percentile(vals, p) == float(np.percentile(vals, p))
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)


def test_ring_bounded_and_list_equal():
    r = Ring(capacity=3)
    for i in range(5):
        r.append(i)
    assert r == [2, 3, 4]          # plain-list equality (legacy assertions)
    assert list(r) == [2, 3, 4]
    assert len(r) == 3 and r[0] == 2
    r.clear()
    assert r == []


def test_registry_idempotent_and_conflicts():
    reg = Registry()
    a = reg.counter("serve_requests_total", labels=("outcome",))
    b = reg.counter("serve_requests_total", labels=("outcome",))
    assert a is b                  # independent modules share one family
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("serve_requests_total", labels=("outcome",))
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("serve_requests_total", labels=("mode",))
    with pytest.raises(ValueError, match="invalid metric name"):
        reg.counter("bad name")
    with pytest.raises(ValueError, match="invalid label name"):
        reg.counter("ok_total", labels=("bad-label",))


def test_label_cardinality_cap():
    reg = Registry()
    fam = reg.counter("fan_out_total", labels=("uid",))
    for i in range(obs_metrics.DEFAULT_LABEL_CARDINALITY):
        fam.labels(uid=str(i)).inc()
    # existing children stay reachable at the cap ...
    fam.labels(uid="0").inc()
    # ... but a NEW label value (unbounded request id) fails loudly
    with pytest.raises(ValueError, match="cardinality cap"):
        fam.labels(uid="overflow")
    with pytest.raises(ValueError, match="expected labels"):
        fam.labels(wrong="x")


def test_registry_reset_keeps_definitions():
    reg = Registry()
    c = reg.counter("n_total")
    h = reg.histogram("lat_ms")
    fam = reg.gauge("depth", labels=("mode",))
    c.inc(5)
    h.observe(1.0)
    fam.labels(mode="paged").set(3)
    reg.reset()
    assert c.value == 0.0 and h.count == 0
    assert fam.labels(mode="paged").value == 0.0
    assert reg.counter("n_total") is c   # definition survived the reset


# ---------------------------------------------------------------------------
# export
# ---------------------------------------------------------------------------

def _tiny_registry():
    reg = Registry()
    reg.counter("requests_total", "Total requests.").inc(3)
    reg.gauge("queue_depth", labels=("mode",)).labels(mode="paged").set(2)
    reg.histogram("latency_ms", "Latency.").observe(5)
    return reg


def test_prometheus_exposition_golden():
    golden = (
        "# HELP requests_total Total requests.\n"
        "# TYPE requests_total counter\n"
        "requests_total 3\n"
        "# TYPE queue_depth gauge\n"
        'queue_depth{mode="paged"} 2\n'
        "# HELP latency_ms Latency.\n"
        "# TYPE latency_ms summary\n"
        'latency_ms{quantile="0.5"} 5\n'
        'latency_ms{quantile="0.95"} 5\n'
        'latency_ms{quantile="0.99"} 5\n'
        "latency_ms_sum 5\n"
        "latency_ms_count 1\n"
    )
    assert to_prometheus(_tiny_registry()) == golden


def test_prometheus_parse_round_trip():
    fams = parse_prometheus(to_prometheus(_tiny_registry()))
    assert fams["requests_total"]["type"] == "counter"
    assert fams["requests_total"]["samples"] == [("requests_total", {}, 3.0)]
    assert fams["queue_depth"]["samples"] == [
        ("queue_depth", {"mode": "paged"}, 2.0)]
    # summary rows (quantiles + _sum/_count) fold under the base family
    names = [s[0] for s in fams["latency_ms"]["samples"]]
    assert names == ["latency_ms"] * 3 + ["latency_ms_sum", "latency_ms_count"]
    assert fams["latency_ms"]["type"] == "summary"


def test_prometheus_parse_rejects_malformed():
    with pytest.raises(ValueError, match="malformed sample"):
        parse_prometheus("what is this line\n")
    with pytest.raises(ValueError, match="bad value"):
        parse_prometheus("ok_metric not_a_number\n")
    with pytest.raises(ValueError, match="malformed TYPE"):
        parse_prometheus("# TYPE name_without_a_type\n")


def test_json_export_is_valid_json():
    snap = json.loads(to_json(_tiny_registry()))
    assert snap["requests_total"]["samples"][0]["value"] == 3.0
    assert snap["latency_ms"]["samples"][0]["count"] == 1.0


def test_metrics_server_scrape():
    from urllib.error import HTTPError
    from urllib.request import urlopen

    with MetricsServer(_tiny_registry(), port=0) as server:
        assert server.port != 0    # ephemeral bind reported a real port
        with urlopen(server.url) as resp:
            assert resp.status == 200
            fams = parse_prometheus(resp.read().decode())
        assert "requests_total" in fams and "latency_ms" in fams
        with urlopen(f"http://{server.host}:{server.port}/metrics.json") as resp:
            assert json.loads(resp.read())["queue_depth"]["type"] == "gauge"
        with pytest.raises(HTTPError):
            urlopen(f"http://{server.host}:{server.port}/nope")


# ---------------------------------------------------------------------------
# trace spans / flight recorder
# ---------------------------------------------------------------------------

def test_span_lifecycle_and_ttft():
    rec = FlightRecorder(capacity=2)
    rec.begin("a", ts=0.0)
    with pytest.raises(ValueError, match="open span"):
        rec.begin("a")             # a leak-in-the-making fails loudly
    rec.event("a", obs_trace.ADMITTED, ts=0.5, slot=1, blocks=3)
    rec.event("a", obs_trace.PREFILL_CHUNK, ts=0.7, size=8)
    rec.event("a", obs_trace.FIRST_TOKEN, ts=1.0)
    rec.event("a", obs_trace.DECODE_STEP, ts=1.2)
    assert rec.get("a").ttft_ms() == 500.0
    assert rec.leaked == ["a"]
    with pytest.raises(ValueError, match="terminal kind"):
        rec.finish("a", obs_trace.DECODE_STEP)
    tr = rec.finish("a", obs_trace.FINISHED, ts=2.0, n_tokens=4)
    assert rec.leaked == []
    assert tr.terminal.kind == obs_trace.FINISHED
    assert tr.terminal_count() == 1
    assert tr.find(obs_trace.ADMITTED).attrs == {"slot": 1, "blocks": 3}
    assert tr.span_ms(obs_trace.ENQUEUED, obs_trace.ADMITTED) == 500.0
    assert tr.span_ms(obs_trace.FIRST_TOKEN, obs_trace.FINISHED) == 1000.0

    with pytest.raises(ValueError, match="unknown span event"):
        tr.event("teleported")

    # the completed ring is bounded: capacity=2 retires the oldest
    for uid in ("b", "c", "d"):
        rec.begin(uid)
        rec.finish(uid, obs_trace.ABANDONED)
    assert [t.uid for t in rec.traces()] == ["c", "d"]
    assert rec.begun_total == 4
    assert rec.finished_by_kind[obs_trace.ABANDONED] == 3


def test_jsonl_dump_and_validate(tmp_path):
    rec = FlightRecorder()
    rec.epoch = 0.0                # deterministic t_ms in the dump
    rec.begin("req-0", ts=0.0)
    rec.event("req-0", obs_trace.ADMITTED, ts=0.1, slot=0)
    rec.event("req-0", obs_trace.FIRST_TOKEN, ts=0.2)
    rec.finish("req-0", obs_trace.FINISHED, ts=0.3)
    rec.begin("req-1", ts=0.0)
    rec.finish("req-1", obs_trace.ABANDONED, ts=0.4)  # never admitted
    path = tmp_path / "trace.jsonl"
    assert rec.dump_jsonl(str(path)) == 2
    assert validate_jsonl(str(path)) == 2
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines[0]["uid"] == "req-0"
    assert [e["kind"] for e in lines[0]["events"]] == [
        "enqueued", "admitted", "first_token", "finished"]
    assert lines[0]["events"][1]["slot"] == 0
    assert lines[0]["events"][1]["t_ms"] == pytest.approx(100.0)


def test_validate_jsonl_rejects_bad_traces(tmp_path):
    def write(obj):
        p = tmp_path / "bad.jsonl"
        p.write_text(json.dumps(obj) + "\n")
        return str(p)

    ev = lambda kind, t: {"kind": kind, "t_ms": t}
    with pytest.raises(ValueError, match="terminal"):
        validate_jsonl(write({"uid": 0, "events": [ev("enqueued", 0),
                                                   ev("admitted", 1)]}))
    with pytest.raises(ValueError, match="unknown event kind"):
        validate_jsonl(write({"uid": 0, "events": [ev("warped", 0)]}))
    with pytest.raises(ValueError, match="not monotone"):
        validate_jsonl(write({"uid": 0, "events": [ev("enqueued", 1),
                                                   ev("finished", 0)]}))
    with pytest.raises(ValueError, match="uid"):
        validate_jsonl(write({"events": [ev("enqueued", 0)]}))


def test_chrome_trace_export():
    rec = FlightRecorder()
    rec.epoch = 0.0
    rec.begin(7, ts=0.0)
    rec.event(7, obs_trace.ADMITTED, ts=0.001)
    rec.event(7, obs_trace.PREFILL_CHUNK, ts=0.002, size=8)
    rec.event(7, obs_trace.FIRST_TOKEN, ts=0.003)
    rec.finish(7, obs_trace.EVICTED, ts=0.004)
    doc = rec.chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    by_name = {}
    for ev in doc["traceEvents"]:
        by_name.setdefault(ev["name"], []).append(ev)
    assert by_name["thread_name"][0]["args"]["name"] == "req 7"
    for phase in ("queued", "prefill", "decode"):
        (slice_ev,) = by_name[phase]
        assert slice_ev["ph"] == "X" and slice_ev["dur"] >= 0
    assert by_name["prefill_chunk"][0]["ph"] == "i"
    assert by_name["evicted"][0]["ph"] == "i"   # non-finish terminal marked


# ---------------------------------------------------------------------------
# TTFT: one definition across every serve path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def granite():
    cfg = reduced_config("granite-3-2b")
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _reqs(cfg, n=3):
    rng = np.random.default_rng(3)
    return [
        Request(uid=i,
                tokens=rng.integers(0, cfg.vocab_size, size=4 + 2 * i).astype(np.int32),
                max_new=3)
        for i in range(n)
    ]


@pytest.mark.parametrize("mode", ["bucketed", "legacy", "chunked", "paged"])
def test_ttft_is_the_trace_span_on_every_path(granite, mode):
    """The satellite regression: engine.py (bucketed) and scheduler.py
    (continuous) historically measured TTFT differently.  Now every
    ``Result.prefill_ms`` IS ``trace.ttft_ms()`` — same events, same
    clock, same number — so the definitions cannot drift."""
    cfg, params = granite
    if mode == "bucketed":
        eng = ServeEngine(params, cfg, max_len=32)
    elif mode == "legacy":
        eng = ServeEngine(params, cfg, max_len=32, continuous=True, n_slots=2)
    elif mode == "chunked":
        eng = ServeEngine(params, cfg, max_len=32, continuous=True,
                          policy=SchedulerPolicy(n_slots=2, chunked_prefill=True,
                                                 chunk_sizes=(8, 1)))
    else:
        eng = ServeEngine(params, cfg, max_len=32, continuous=True,
                          policy=SchedulerPolicy(n_slots=2, chunked_prefill=True,
                                                 chunk_sizes=(8, 1), paged=True,
                                                 block_size=4, n_blocks=12))
    reqs = _reqs(cfg)
    out = eng.generate(reqs)
    assert len(out) == len(reqs)
    rec = eng.obs.recorder
    assert rec.leaked == []
    by_uid = {tr.uid: tr for tr in rec.traces()}
    for r in out:
        tr = by_uid[r.uid]
        assert tr.terminal.kind == obs_trace.FINISHED
        assert tr.terminal_count() == 1
        assert tr.find(obs_trace.FIRST_TOKEN) is not None
        assert r.prefill_ms == tr.ttft_ms()   # bitwise — derived, not re-timed
        assert tr.ttft_ms() > 0.0
    # and the registry saw the same number of TTFT observations
    h = eng.obs.registry.histogram("serve_ttft_ms")
    assert h.count == len(reqs)
    c = eng.obs.registry.counter("serve_requests_total", labels=("outcome",))
    assert c.labels(outcome="finished").value == len(reqs)


def test_engines_never_share_obs_state(granite):
    cfg, params = granite
    a = ServeEngine(params, cfg, max_len=32)
    b = ServeEngine(params, cfg, max_len=32)
    assert a.obs.registry is not b.obs.registry
    assert a.obs.recorder is not b.obs.recorder


# ---------------------------------------------------------------------------
# scheduler phase spans: profiler clock + registry
# ---------------------------------------------------------------------------

def test_span_adds_inclusive_seconds_to_its_instrument(monkeypatch):
    """No profiler is running: the span still times its block, nested
    spans each add their own inclusive duration, and an exception leaves
    the block with its time recorded."""
    obs = Observability()
    clock = iter([10.0, 10.5, 11.0, 13.0, 20.0, 20.25])
    monkeypatch.setattr(obs_trace, "perf_counter", lambda: next(clock))
    fam = obs.registry.counter("serve_host_seconds_total", labels=("phase",))
    decode, sync = fam.labels(phase="decode"), fam.labels(phase="sync")
    with obs.span("decode", decode.inc):
        with obs.span("sync", sync.inc) as s:
            s.annotate(lanes=3)
    assert sync.value == 0.5 and decode.value == 3.0
    with pytest.raises(KeyError):
        with obs.span("sync", sync.inc):
            raise KeyError("lane")
    assert sync.value == 0.75 and decode.value == 3.0


def _paged_engine(granite):
    cfg, params = granite
    return ServeEngine(params, cfg, max_len=32, continuous=True,
                       policy=SchedulerPolicy(n_slots=2, chunked_prefill=True,
                                              chunk_sizes=(8, 1), paged=True,
                                              block_size=4, n_blocks=16))


def _span_reqs(cfg):
    # prompts of 3-7 rows and 6-9 new tokens: every lane crosses blocks
    rng = np.random.default_rng(5)
    return [Request(uid=i, tokens=rng.integers(0, cfg.vocab_size, size=3 + i).astype(np.int32),
                    max_new=6 + i % 4) for i in range(5)]


def test_stream_times_every_step_and_its_phases(granite, monkeypatch):
    eng = _paged_engine(granite)
    sched = eng.scheduler
    iterations = []
    admit = sched._admit  # called once per loop iteration
    monkeypatch.setattr(sched, "_admit", lambda *a: iterations.append(1) or admit(*a))
    out = eng.generate(_span_reqs(granite[0]))
    assert len(out) == 5
    reg = eng.obs.registry
    step = reg.histogram("serve_step_ms")
    assert step.count == len(iterations) > 0
    host = reg.counter("serve_host_seconds_total", labels=("phase",))
    s = {p: host.labels(phase=p).value for p in
         ("admit", "blocks", "prefill", "decode", "sync", "finish")}
    assert s["admit"] + s["prefill"] + s["decode"] + s["finish"] <= step.sum / 1e3 + 1e-9
    assert s["blocks"] + s["sync"] <= s["prefill"] + s["decode"] + 1e-9
    assert s["blocks"] > 0 and s["sync"] > 0 and s["finish"] > 0


def test_stream_spans_land_on_the_profiler_host_plane(granite, tmp_path):
    eng = _paged_engine(granite)
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = eng.generate(_span_reqs(granite[0]))
    finally:
        jax.profiler.stop_trace()
    assert len(out) == 5
    (path,) = tmp_path.glob("**/*.xplane.pb")
    profile = jax.profiler.ProfileData.from_file(str(path))
    events = [e for plane in profile.planes if plane.name.startswith("/host:CPU")
              for line in plane.lines for e in line.events]
    names = {e.name for e in events}
    assert {"serve.step", "serve.admit", "serve.prefill", "serve.decode", "serve.blocks",
            "serve.sync", "serve.finish"} <= names
    steps = [e for e in events if e.name == "serve.step"]
    assert len(steps) == eng.obs.registry.histogram("serve_step_ms").count
    args = [name for name, _ in steps[-1].stats]
    assert sorted(args) == ["chunk", "decoding", "fused", "prefilling", "step"]
    # fused=<lanes> sums to the prompt tokens the decode program consumed
    fused = sum(int(v) for e in steps for name, v in e.stats if name == "fused")
    assert fused == eng.obs.registry.counter(
        "serve_prefill_tokens_total", labels=("path",)).labels(path="decode").value > 0


# ---------------------------------------------------------------------------
# quantization-quality probe
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def packed_granite(granite):
    cfg, params = granite
    return cfg, params, pack_model_params(params, 4)


def test_quality_probe_full_planes_exact_and_monotone(packed_granite):
    cfg, _, packed = packed_granite
    rng = np.random.default_rng(5)
    toks = rng.integers(0, cfg.vocab_size, size=(1, 8)).astype(np.int32)
    reg = Registry()
    rows = quality_probe(packed, cfg, toks, plane_counts=[1, 2, 4],
                         registry=reg)
    by_k = {r.planes: r for r in rows}
    assert set(by_k) == {1, 2, 4}
    # full planes ARE the full-precision packed model: exact agreement
    assert by_k[4].logit_mse == 0.0
    assert by_k[4].top1_agreement == 1.0
    # dropping planes never improves the logits
    assert by_k[1].logit_mse >= by_k[2].logit_mse >= by_k[4].logit_mse
    # rows export through the same registry path as serve metrics
    text = to_prometheus(reg)
    assert 'serve_quality_top1{group="all",planes="4"} 1' in text
    assert "serve_quality_logit_mse" in text
    assert rows == sorted(rows, key=lambda r: (r.group, r.planes))
    assert by_k[2].to_dict()["group"] == "all"


def test_quality_probe_layer_groups(packed_granite):
    cfg, _, packed = packed_granite
    toks = np.zeros((1, 4), np.int32)
    rows = quality_probe(packed, cfg, toks, plane_counts=[4],
                         groups=("attn", "mlp"))
    # truncating to ALL planes is the identity regardless of group
    assert all(r.logit_mse == 0.0 and r.top1_agreement == 1.0 for r in rows)
    assert [r.group for r in rows] == ["attn", "mlp"]


def test_quality_probe_errors(packed_granite):
    cfg, float_params, packed = packed_granite
    toks = np.zeros((1, 4), np.int32)
    with pytest.raises(ValueError, match="packed model"):
        quality_probe(float_params, cfg, toks)    # float params, no planes
    with pytest.raises(ValueError, match="unknown layer group"):
        quality_probe(packed, cfg, toks, groups=("embeddings",))
    with pytest.raises(ValueError, match=">= 1"):
        quality_probe(packed, cfg, toks, plane_counts=[0, 2])


def test_truncate_packed_view_semantics(packed_granite):
    _, _, packed = packed_granite
    pw = packed_leaves(packed)[0]
    assert truncate_packed(pw, pw.n_bits) is pw     # k >= n_bits: identity
    assert truncate_packed(pw, pw.n_bits + 3) is pw
    n, k = pw.n_bits, 2
    t = truncate_packed(pw, k)
    assert t.n_bits == k
    # top-k planes kept (LSB-first layout: the last k); the dropped LSBs
    # fold into the scale as a PURE power of two (exact in float) while
    # the original denominator rides in denom_bits — the property that
    # makes the static view bitwise-equal to the kernels' runtime
    # active-plane masking.
    np.testing.assert_array_equal(np.asarray(t.planes),
                                  np.asarray(pw.planes[..., n - k:, :, :]))
    assert t.denom_bits == n
    np.testing.assert_array_equal(np.asarray(t.scale),
                                  np.asarray(pw.scale) * 2.0 ** (n - k))
    # the dequantised view equals the full dequantisation with the low
    # planes zeroed
    zeroed = dataclasses.replace(
        pw, planes=pw.planes.at[..., : n - k, :, :].set(0))
    np.testing.assert_array_equal(np.asarray(unpack_to_float(t)),
                                  np.asarray(unpack_to_float(zeroed)))
    with pytest.raises(ValueError, match="k >= 1"):
        truncate_packed(pw, 0)


# ---------------------------------------------------------------------------
# label-capacity management (the quality-probe cardinality fix)
# ---------------------------------------------------------------------------

def test_family_ensure_capacity_grows_never_shrinks():
    reg = Registry()
    fam = reg.counter("fan_out_total", labels=("uid",))
    fam.ensure_capacity(obs_metrics.DEFAULT_LABEL_CARDINALITY + 10)
    assert fam.max_children == obs_metrics.DEFAULT_LABEL_CARDINALITY + 10
    for i in range(obs_metrics.DEFAULT_LABEL_CARDINALITY + 10):
        fam.labels(uid=str(i)).inc()
    with pytest.raises(ValueError, match="cardinality cap"):
        fam.labels(uid="overflow")
    # capacity only ratchets up — "shrinking" below live children would
    # orphan them
    fam.ensure_capacity(1)
    assert fam.max_children == obs_metrics.DEFAULT_LABEL_CARDINALITY + 10
    fam.labels(uid="0").inc()


def test_registry_max_children_kwarg():
    reg = Registry()
    fam = reg.gauge("planes_g", labels=("k",), max_children=3)
    assert fam.max_children == 3
    # re-registration never silently narrows an existing family
    again = reg.gauge("planes_g", labels=("k",), max_children=2)
    assert again is fam and fam.max_children == 3
    with pytest.raises(ValueError, match="max_children"):
        reg.counter("plain_total", max_children=5)   # unlabeled: no children


def test_quality_probe_wide_sweep_exceeds_default_cap(granite):
    """The regression: a wide probe (many plane counts x every layer
    group) enumerates more label combinations than
    DEFAULT_LABEL_CARDINALITY — it must size its families to the
    enumerable label space up front instead of tripping the cap
    mid-serve.  Counts past n_bits are identity views, so the label
    space widens without packing a wider model."""
    cfg, params = granite
    packed = pack_model_params(params, 4)
    toks = np.zeros((1, 4), np.int32)
    reg = Registry()
    groups = ("all", "attn", "mlp", "head")
    counts = list(range(1, 18))  # 17 x 4 = 68 children > the default 64
    assert len(counts) * len(groups) > obs_metrics.DEFAULT_LABEL_CARDINALITY
    rows = quality_probe(packed, cfg, toks, plane_counts=counts,
                         groups=groups, registry=reg)
    assert len(rows) == len(counts) * len(groups)
    fam = reg.gauge("serve_quality_top1", labels=("planes", "group"))
    assert len(list(fam.children())) == len(counts) * len(groups)
    assert fam.max_children >= len(counts) * len(groups)
    # an earlier, narrower registration of the same family must be GROWN
    # (ensure_capacity), not tripped by the probe's new children
    reg2 = Registry()
    reg2.gauge("serve_quality_top1", labels=("planes", "group"),
               max_children=2)
    reg2.gauge("serve_quality_logit_mse", labels=("planes", "group"),
               max_children=2)
    quality_probe(packed, cfg, toks, plane_counts=[1, 2, 3], registry=reg2)


def test_precision_tiers_from_probe(granite):
    from repro.obs.quality import QualityRow, precision_tiers_from_probe

    rows = [QualityRow(planes=k, group="all", logit_mse=0.0,
                       top1_agreement=a)
            for k, a in [(1, 0.61), (2, 0.83), (3, 0.96), (4, 1.0)]]
    # smallest plane count clearing each class's agreement bar
    tiers = precision_tiers_from_probe(
        rows, {"economy": 0.95, "draft": 0.60})
    assert tiers == {"economy": 3, "draft": 1}
    # nothing clears the bar: fall back to the largest probed count
    low = [dataclasses.replace(r, top1_agreement=min(r.top1_agreement, 0.9))
           for r in rows]
    assert precision_tiers_from_probe(low, {"x": 0.95})["x"] == 4
    with pytest.raises(ValueError, match="not in \\[0, 1\\]"):
        precision_tiers_from_probe(rows, {"x": 1.5})
    with pytest.raises(ValueError, match="'all'-group rows"):
        precision_tiers_from_probe(
            [dataclasses.replace(rows[0], group="attn")], {"x": 0.5})
    # end-to-end: probe a real packed model, derive tiers, and the result
    # is directly consumable by SchedulerPolicy
    cfg, params = granite
    packed = pack_model_params(params, 4)
    toks = np.zeros((1, 4), np.int32)
    probe_rows = quality_probe(packed, cfg, toks, plane_counts=[2, 4])
    tiers = precision_tiers_from_probe(probe_rows, {"economy": 0.0})
    assert tiers["economy"] == 2
    SchedulerPolicy(n_slots=2, chunked_prefill=True, chunk_sizes=(8, 1),
                    precision_tiers=tiers)
