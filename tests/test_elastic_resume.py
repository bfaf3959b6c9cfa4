"""Elastic resume end-to-end drill (ROADMAP item): checkpoint a model on
mesh A, restore it onto a DIFFERENT mesh B via ``ckpt.restore(mesh=...)``
(which routes through ``dist.elastic.reshard_tree``), and assert the
serve engine decodes token-exactly after the move.  Greedy decoding is
layout-invariant, so any divergence is a resharding bug, not noise."""
import os
import subprocess
import sys
import textwrap

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


def test_ckpt_on_mesh_a_restores_on_mesh_b_token_exact(tmp_path):
    out = _run_subprocess(f"""
        import jax, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.ckpt import checkpoint as ckpt
        from repro.configs import reduced_config
        from repro.dist.elastic import reshard_tree
        from repro.models import init_params
        from repro.serve import Request, ServeEngine

        cfg = reduced_config("granite-3-2b")
        params = init_params(jax.random.PRNGKey(0), cfg)

        def reqs():
            return [Request(uid=i, tokens=(np.arange(8, dtype=np.int32) + 3 * i)
                            % cfg.vocab_size, max_new=6) for i in range(4)]

        # Mesh A: shard, serve, checkpoint (ckpt stores logically-unsharded).
        mesh_a = make_mesh((2, 4), ("data", "model"))
        params_a = reshard_tree(params, mesh_a)
        ref = ServeEngine(params_a, cfg, max_len=32, mesh=mesh_a).generate(reqs())
        ckpt.save(params_a, r"{tmp_path}", step=7, shards=2)

        # Mesh B (different shape): restore with mesh= -> reshard_tree path.
        mesh_b = make_mesh((4, 2), ("data", "model"))
        params_b = ckpt.restore(jax.eval_shape(lambda: params), r"{tmp_path}",
                                step=7, mesh=mesh_b)
        moved = ServeEngine(params_b, cfg, max_len=32, mesh=mesh_b).generate(reqs())
        for a, b in zip(ref, moved):
            np.testing.assert_array_equal(a.tokens, b.tokens)

        # And the continuous scheduler decodes identically on the new mesh.
        cont = ServeEngine(params_b, cfg, max_len=32, mesh=mesh_b,
                           continuous=True, n_slots=4).generate(reqs())
        for a, b in zip(ref, sorted(cont, key=lambda r: r.uid)):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        print("ELASTIC_RESUME_OK")
    """)
    assert "ELASTIC_RESUME_OK" in out
