"""Sharded serving + per-request sampling.

The SPMD test spawns a subprocess with 8 host devices (XLA_FLAGS must be
set before jax initialises); the sampling tests run in-process on 1
device."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np

from repro.configs import reduced_config
from repro.models import init_params
from repro.serve import Request, ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_engine_serves_on_2x4_mesh():
    """Engine output on a ("data", "model") mesh matches the single-device
    engine token-for-token (greedy decoding is layout-invariant)."""
    out = _run_subprocess("""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import reduced_config
        from repro.models import init_params
        from repro.serve import Request, ServeEngine
        cfg = reduced_config("granite-3-2b")
        params = init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        def reqs():
            return [Request(uid=i, tokens=(np.arange(8, dtype=np.int32) + i) % cfg.vocab_size,
                            max_new=6) for i in range(4)]
        ref = ServeEngine(params, cfg, max_len=32).generate(reqs())
        sharded = ServeEngine(params, cfg, max_len=32, mesh=mesh).generate(reqs())
        for a, b in zip(ref, sharded):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        # indivisible bucket (3 rows on data=2): batch axis replicates,
        # output still matches single-device token-for-token
        odd = ServeEngine(params, cfg, max_len=32, mesh=mesh).generate(reqs()[:3])
        for a, b in zip(ref[:3], odd):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        print("SHARDED_SERVE_OK")
    """)
    assert "SHARDED_SERVE_OK" in out


def test_packed_decode_on_2x4_mesh_matches_single_device():
    """Tentpole acceptance: packed decode on a ("data", "model") mesh with
    model>1 is token-identical to single-device packed decode, with the
    planes/sign byte tensors actually SHARDED (not replicated) under the
    dist rules — the shard_map'd bitserial matmul runs on per-shard
    PackedWeights.  Also covers continuous batching (the slot pool must
    stay token-exact over packed weights) and the shard-aware exporter
    (slice-then-pack == pack-then-slice)."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.configs import reduced_config
        from repro.core import export_packed, export_packed_sharded
        from repro.core.bitrep import decompose
        from repro.core.packing import PackedWeight, pack_model_params
        from repro.models import init_params
        from repro.serve import Request, ServeEngine
        cfg = reduced_config("granite-3-2b")
        params = init_params(jax.random.PRNGKey(0), cfg)
        packed = pack_model_params(params, 6)
        mesh = make_mesh((2, 4), ("data", "model"))
        def reqs():
            return [Request(uid=i, tokens=(np.arange(4 + 2 * i, dtype=np.int32) + i)
                            % cfg.vocab_size, max_new=5) for i in range(5)]
        ref = {r.uid: r.tokens for r in ServeEngine(packed, cfg, max_len=32).generate(reqs())}
        eng = ServeEngine(packed, cfg, max_len=32, mesh=mesh)
        pw = eng.params["blocks"]["p0"]["mixer"]["wq"]
        assert pw.kn_spec == ("data", "model"), pw.kn_spec
        for leaf in (pw.planes, pw.sign):
            assert not leaf.sharding.is_fully_replicated, leaf.sharding
        assert pw.planes.addressable_shards[0].data.nbytes * 8 == pw.planes.nbytes
        for r in eng.generate(reqs()):
            np.testing.assert_array_equal(ref[r.uid], r.tokens)
        # continuous batching over the same packed weights, staggered arrivals
        cont = ServeEngine(packed, cfg, max_len=32, mesh=mesh, continuous=True, n_slots=4)
        for r in cont.generate(reqs(), arrival_steps=[0, 0, 1, 3, 5]):
            np.testing.assert_array_equal(ref[r.uid], r.tokens)
        assert cont.scheduler.compiled_decode_programs() == 1
        # chunked prefill on the mesh over packed weights: multi-admit +
        # interleaved prefill/decode must stay token-identical with a
        # bounded prefill program set (tentpole acceptance)
        from repro.serve import SchedulerPolicy
        chk = ServeEngine(packed, cfg, max_len=32, mesh=mesh, continuous=True,
                          policy=SchedulerPolicy(n_slots=4, chunked_prefill=True,
                                                 chunk_sizes=(8, 1)))
        for r in chk.generate(reqs(), arrival_steps=[0, 0, 1, 3, 5]):
            np.testing.assert_array_equal(ref[r.uid], r.tokens)
        assert chk.scheduler.compiled_decode_programs() == 1
        assert chk.scheduler.compiled_prefill_programs() <= 2
        assert chk.scheduler.compiled_admit_programs() == 1
        # shard-aware export: per-slice local packing assembles the same
        # bytes as the global exporter, already mesh-sharded
        w = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64), jnp.float32)
        w = w.at[1].mul(50.0)
        reps = {"blocks/p0/mixer/wq": decompose(w, 4, group_axes=(0,))}
        g = export_packed(reps)["blocks/p0/mixer/wq"]
        s = export_packed_sharded(reps, mesh)["blocks/p0/mixer/wq"]
        np.testing.assert_array_equal(np.asarray(g.planes), np.asarray(s.planes))
        np.testing.assert_array_equal(np.asarray(g.sign), np.asarray(s.sign))
        np.testing.assert_array_equal(np.asarray(g.scale), np.asarray(s.scale))
        assert not s.planes.sharding.is_fully_replicated
        print("SHARDED_PACKED_OK")
    """)
    assert "SHARDED_PACKED_OK" in out


def _greedy_tokens(engine, prompt, uid=0):
    [res] = engine.generate([Request(uid=uid, tokens=prompt, max_new=6, temperature=0.0)])
    return res.tokens


def test_per_request_temperature_in_one_bucket():
    """A greedy request keeps its greedy output even when bucketed with a
    hot-temperature request (regression: bucket[0].temperature applied to
    every row)."""
    cfg = reduced_config("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = (np.arange(8, dtype=np.int32)) % cfg.vocab_size
    solo = _greedy_tokens(ServeEngine(params, cfg, max_len=32), prompt)

    engine = ServeEngine(params, cfg, max_len=32, seed=7)
    reqs = [
        Request(uid=0, tokens=prompt.copy(), max_new=6, temperature=5.0),  # hot row FIRST
        Request(uid=1, tokens=prompt.copy(), max_new=6, temperature=0.0),  # greedy row
    ]
    results = {r.uid: r for r in engine.generate(reqs)}
    np.testing.assert_array_equal(results[1].tokens, solo)
    assert (results[1].tokens >= 0).all() and (results[1].tokens < cfg.vocab_size).all()
    assert (results[0].tokens >= 0).all() and (results[0].tokens < cfg.vocab_size).all()


def test_all_greedy_bucket_is_deterministic():
    cfg = reduced_config("granite-3-2b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = (np.arange(8, dtype=np.int32) * 3) % cfg.vocab_size
    a = _greedy_tokens(ServeEngine(params, cfg, max_len=32, seed=1), prompt)
    b = _greedy_tokens(ServeEngine(params, cfg, max_len=32, seed=2), prompt)
    np.testing.assert_array_equal(a, b)
