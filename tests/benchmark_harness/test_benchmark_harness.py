"""Tests of the benchmark's yardstick, on the CPU, with no chip library.

    python -m pytest -q tests/benchmark_harness
"""
import math
import pathlib

import numpy as np
import pytest

from bench import costs, devtrace, measure, peaks, traffic
from bench.references import dense

DATA = pathlib.Path(__file__).parent / "data"

MIX = {"loop": "closed", "clients": 4, "requests": 24,
       "prompt": {"dist": "lognormal", "median": 160, "sigma": 0.55, "min": 64, "max": 512},
       "output": {"dist": "uniform", "min": 128, "max": 512}}


# -- traffic -----------------------------------------------------------------

def test_traffic_is_deterministic_per_seed():
    a = traffic.generate(MIX, 2**31 + 11, vocab=1000)
    b = traffic.generate(MIX, 2**31 + 11, vocab=1000)
    assert [(r.uid, r.max_new, r.prompt.tolist()) for r in a] == \
           [(r.uid, r.max_new, r.prompt.tolist()) for r in b]


def test_seeds_share_the_lengths_and_draw_the_tokens():
    a = traffic.generate(MIX, 1, vocab=1000)
    b = traffic.generate(MIX, 2, vocab=1000)
    assert [(len(r.prompt), r.max_new) for r in a] == [(len(r.prompt), r.max_new) for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = MIX["clients"]
    # the clients' first requests are a stratified set of their own
    assert sorted(r.max_new for r in a[:c]) == traffic.residual(MIX["output"], c)
    assert sorted(r.max_new for r in a[c:]) == traffic.stratified(MIX["output"], len(a) - c)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in a)


def test_stratified_lengths_follow_the_distribution():
    s = traffic.stratified(MIX["prompt"], 101)
    assert s[50] == 160  # the median
    assert min(s) >= 64 and max(s) <= 512 and s == sorted(s)
    u = traffic.stratified(MIX["output"], 4)
    assert u == [176, 272, 368, 464]


def test_traffic_rejects_an_open_loop():
    with pytest.raises(ValueError):
        traffic.validate(dict(MIX, loop="open"))
    with pytest.raises(ValueError):
        traffic.validate(dict(MIX, start="fresh"))


def test_residual_lengths_are_met_in_proportion_to_length():
    # a remaining length r is met with weight P(length >= r): uniform
    # 128..512 leaves a mean of about (E[L^2] / E[L]) / 2
    rem = traffic.residual(MIX["output"], 1000)
    assert min(rem) >= 1 and max(rem) <= 512
    lengths = traffic.stratified(MIX["output"], traffic.RESIDUAL_POOL)
    want = sum(n * n for n in lengths) / sum(lengths) / 2
    assert abs(np.mean(rem) - want) < 2


def test_every_cell_names_files_that_exist():
    import json

    root = pathlib.Path(__file__).resolve().parents[2]
    bench = json.loads((root / "BENCHMARK.json").read_text())
    configs = {c["name"]: c for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert (root / configs[cell["config"]]["file"]).is_file()
        mix = json.loads((root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
        traffic.validate(mix)
    for m in bench["per_layer"]:
        assert (root / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]
    for path in bench["paths"]:
        assert (root / path).is_dir()


# -- end-to-end metrics from recorder events ---------------------------------

def _events(step=0.05, n_steps=40, lanes=4, t0=10.0):
    """Lanes admitted at t0, first token 0.2 s later, then a decode step
    every ``step`` s."""
    ev = {}
    for uid in range(lanes):
        t = t0
        evs = [("enqueued", t, {}), ("admitted", t, {})]
        t += 0.2
        evs.append(("first_token", t, {}))
        for i in range(n_steps):
            t += step
            evs.append(("decode_step", t, {}))
        ev[uid] = evs
    return ev


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100):
        xs = rng.normal(size=n).tolist()
        for p in (50, 90, 95, 99):
            assert math.isclose(measure.percentile(xs, p), float(np.percentile(xs, p)),
                                rel_tol=1e-12, abs_tol=1e-12)


def test_tokens_gaps_and_ttft_from_events():
    ev = _events()
    t_open, t_close = 10.0, 10.2 + 40 * 0.05 + 1e-9
    e = measure.end_to_end(ev, t_open, t_close)
    assert e["output_tok_per_s"] == pytest.approx(4 * 41 / (t_close - t_open))
    assert e["n_gaps"] == 4 * 40
    assert e["tpot_p95_ms"] == pytest.approx(50.0)
    assert e["n_ttft"] == 4 and e["ttft_p95_ms"] == pytest.approx(200.0)
    # a window that opens after the first tokens holds no TTFT and fewer gaps
    late = measure.end_to_end(ev, 10.5, t_close)
    assert late["n_ttft"] == 0 and late["ttft_p95_ms"] is None
    assert late["n_gaps"] < e["n_gaps"]


def test_a_stall_in_the_window_raises_the_tail():
    base = measure.end_to_end(_events(n_steps=20), 10.0, 20.0)
    # 4 lanes x 21 gaps: a 1 s stall before two of each lane's steps
    # lengthens 8 of 84 gaps, more than the 5% beyond the 95th percentile
    ev = _events(n_steps=20)
    for uid, evs in ev.items():
        for k in (5 + uid, 12 + uid):
            evs[k:] = [(kind, ts + 1.0, a) for kind, ts, a in evs[k:]]
    stalled = measure.end_to_end(ev, 10.0, 20.0)
    assert stalled["tpot_p95_ms"] > base["tpot_p95_ms"] + 100
    assert stalled["output_tok_per_s"] == base["output_tok_per_s"]


def test_stalls_are_steps_past_the_median():
    ev = _events(n_steps=20)
    for uid, evs in ev.items():
        # lanes stamped 0.1 ms apart; one 0.3 s stall before step 9
        evs[:] = [(k, ts + 1e-4 * uid + (0.3 if i >= 12 else 0.0), a)
                  for i, (k, ts, a) in enumerate(evs)]
    s = measure.stalls(ev, 10.0, 20.0)
    assert s["steps"] == 20 and s["median_ms"] == pytest.approx(50.0)
    assert s["n"] == 1 and s["excess_s"] == pytest.approx(0.3)


def test_spread_is_the_quartile_distance_over_the_median():
    assert measure.spread([1.0, 2.0, 3.0, 4.0, 5.0, 6.0]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


# -- device trace reduction --------------------------------------------------

OP = ("%bitserial_matmul_pallas.67 = bf16[16,8192]{1,0:T(8,128)(2,1)S(1)} custom-call("
      "bf16[16,2048]{1,0:T(8,128)(2,1)S(1)} %reshape.382, u8[6,256,8192]{2,1,0:T(8,128)(4,1)S(1)}"
      " %dynamic-slice_bitcast_fusion.36, u8[256,8192]{1,0:T(8,128)(4,1)S(1)} "
      "%dynamic-slice_bitcast_fusion.37, f32[1,8192]{1,0:T(1,128)S(1)} %broadcast_in_dim.168), "
      "custom_call_target=\"tpu_custom_call\"")
ATTN = ("%paged_attention_pallas.9 = bf16[16,8,4,64]{3,2,1,0} custom-call(s32[16,20]{1,0} %a, "
        "s32[16]{0} %b, bf16[16,8,4,64]{3,2,1,0} %c, bf16[320,8,32,64]{3,2,1,0} %d)")


def test_bitserial_shape_from_the_instruction_text():
    assert devtrace.bitserial_shape(OP) == (16, 2048, 8192, 6)
    assert devtrace.short(OP) == "%bitserial_matmul_pallas.67 bf16[16,8192]"


def test_reduce_events_attributes_programs_kernels_and_gaps():
    host = [("bench_window_open", 0.0, 1.0), ("bench_window_close", 9.0, 9.5),
            ("PjRtExecute", 4.5, 5.5), ("outer", 0.0, 20.0)]
    device = {
        "XLA Modules": [("jit__decode_fn(1)", 0.5, 2.0), ("jit__decode_fn(1)", 2.5, 4.0),
                        ("jit_chunk_into_pool(7)", 6.0, 8.0)],
        "XLA Ops": [("%while.1 = (s32[]) while((s32[]) %t)", 0.5, 2.0),
                    (OP, 0.6, 1.0), (ATTN, 1.0, 1.5), (OP, 2.5, 3.0), (ATTN, 3.0, 4.0),
                    (OP, 6.0, 7.0), ("%fusion.3 = f32[4]{0} fusion()", 7.0, 8.0)],
    }
    r = devtrace.reduce_events(device, host)
    assert r["window_s"] == pytest.approx(8.0)
    # the first decode straddles the open: only its part after 1.0 counts
    assert r["busy_s"] == pytest.approx(1.0 + 1.5 + 2.0)
    assert r["programs"]["decode"] == {"n": 2, "device_s": pytest.approx(2.5)}
    assert r["programs"]["chunk"] == {"n": 1, "device_s": pytest.approx(2.0)}
    kd = r["kernels"][("decode", "bitserial")]
    assert kd["n"] == 1 and kd["calls"] == {OP: 1} and kd["device_s"] == pytest.approx(0.5)
    assert r["kernels"][("decode", "paged_attention")]["device_s"] == pytest.approx(1.5)
    assert r["kernels"][("chunk", "bitserial")]["n"] == 1
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["PjRtExecute", pytest.approx(2.0)]  # 4.0 .. 6.0
    assert all(not name.startswith("decode:%while") for name, _ in r["breakdown"]["device_ops"])


def test_reduce_of_a_recorded_cpu_profile():
    device_free = devtrace.load(str(DATA))
    with pytest.raises(RuntimeError, match="no TPU device plane"):
        devtrace.split(device_free)
    host = [ev for plane in device_free.planes if plane.name.startswith("/host:CPU")
            for ln in plane.lines for ev in devtrace._events(ln)]
    t0, t1 = devtrace.window(host)
    assert 0 < t1 - t0 < 5.0
    # the jitted program ran in the window, on the recorded CPU threads
    ran = [s for name, s, e in host if name.startswith("dot_general")]
    assert ran and all(t0 <= s <= t1 for s in ran)


# -- operations and bytes ----------------------------------------------------

def test_bitserial_counts():
    flops, nbytes = costs.bitserial(16, 2048, 8192, 6)
    assert flops == 2 * 16 * 2048 * 8192
    assert nbytes == 7 * 2048 * 8192 / 8 + 4 * 8192 + 2 * 16 * 2048 + 2 * 16 * 8192


def test_paged_attention_counts():
    flops, nbytes = costs.paged_attention(rows=1000, n_heads=32, n_kv=8, head_dim=64)
    assert flops == 4 * 32 * 64 * 1000
    assert nbytes == 2 * 2 * 8 * 64 * 1000


def test_least_time_names_its_roof():
    p = peaks.peaks("TPU v5 lite")
    t, roof = costs.least_time(*costs.bitserial(16, 2048, 8192, 6), p)
    assert roof == "memory" and t == pytest.approx(costs.bitserial(16, 2048, 8192, 6)[1] / 819e9)
    t, roof = costs.least_time(*costs.bitserial(4096, 6144, 24576, 4), p)
    assert roof == "compute" and t == pytest.approx(2 * 4096 * 6144 * 24576 / 197e12)


def test_model_flops_counts_weights_and_attention():
    model = {"d_model": 8, "n_layers": 2, "n_heads": 2, "head_dim": 4, "vocab": 10,
             "vocab_rows": 16}
    dims = {"matrices": ("wq",), "wq": (8, 8), "n_layers": 2, "attn_layers": 2}
    # two layers of one 8x8 matrix and a tied 8x10 head; attention 4*2*2*4*(p+1)
    assert dense.model_flops(model, dims, [0, 3]) == \
        2 * (2 * (2 * 64 + 80)) + 64 * (1 + 4)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
