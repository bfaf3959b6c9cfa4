"""The comparison that decides ``correct``, exercised on the CPU at a
small size through the harness's own functions (no chip is looked for).

The control (the reference with every matmul operand in float8) must
read a wider gap than the limit, and a run whose timed path is broken
underneath must come out not correct."""

import jax
import pytest

from bench import check, faults, run, traffic

TINY = {
    "name": "tiny", "reference": "dense", "n_bits": 4,
    "program": {"arch": "granite-3-2b", "overrides": {
        "d_model": 128, "n_heads": 4, "n_kv_heads": 2, "d_ff": 256, "n_layers": 2,
        "vocab_size": 512, "norm_eps": 1e-5}},
    "model": {"d_model": 128, "n_heads": 4, "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
              "n_layers": 2, "vocab": 512, "vocab_rows": 512, "mlp": "swiglu",
              "tied_embedding": True, "rope_theta": 10000.0, "norm_eps": 1e-5},
    # Between the program's widest gap at this size (about 0.02 on CPU)
    # and the fp8 control's (see test_control_reads_above_the_limit).
    "limits": {"max_logit_gap": 0.1},
}
MIX = {"loop": "closed", "clients": 4, "requests": 400,
       "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5, "min": 8, "max": 40},
       "output": {"dist": "uniform", "min": 8, "max": 24}}
E2E = [{"name": n, "unit": u} for n, u in
       (("output_tok_per_s", "tokens/s"), ("tpot_p95_ms", "ms"), ("setup_s", "s"))]
PEAK = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
SECONDS = 2.0


def _run(seed):
    return run.run_cell("tiny", seed, SECONDS, 0, TINY, MIX, E2E, [], {},
                        jax.devices("cpu")[0], 1, PEAK)


def test_rehearsal_is_correct_and_reports_every_metric():
    result, lines = _run(2**31 + 5)
    assert result["correct"] is True, lines
    assert set(result["metrics"]) == {"output_tok_per_s", "tpot_p95_ms", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > MIX["clients"] and result["failed"] == 0
    assert list(result)[-1] == "checks" and lines[0].startswith("check max_logit_gap")


def test_control_reads_above_the_limit():
    watch = run.WindowWatch()
    engine, params, _ = run.build(TINY, MIX, 7, jax.devices("cpu")[0])
    got = run.serve_window(engine, MIX, 7, SECONDS, TINY["model"]["vocab"], watch)
    read = check.served_gaps(TINY, 7, got["prompts"], got["finished"], traffic.max_len(MIX),
                             control=True)
    assert read["max_logit_gap"] <= TINY["limits"]["max_logit_gap"]
    assert read["control_max_logit_gap"] > 3 * TINY["limits"]["max_logit_gap"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    """A token altered where it is sampled, and a decode step that returns
    its KV cache unchanged, each read not correct."""
    monkeypatch.setattr(*faults.FAULTS[fault]())
    result, lines = _run({"altered-token": 11, "stale-cache": 13}[fault])
    assert result["correct"] is False, lines
