"""Launcher entry points called in-process: ``main(argv)`` of both
launchers, the depth cut and tensor-parallel share of the trainer, and
where the persistent compilation cache is placed."""
import pathlib

import jax
import numpy as np
import pytest

from repro.configs import get_config, tp_share
from repro.launch import cache


@pytest.fixture
def no_cache(monkeypatch, tmp_path):
    """Launchers turn the persistent cache on; with the variable set after
    jax read its config they leave jax's (empty) setting alone, so these
    tests write no cache."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def test_cache_dir_from_environment_is_kept(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.setup_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_defaults_to_fixed_checkout_path(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(pathlib.Path(__file__).resolve().parents[1] / ".jax_cache")
    try:
        assert cache.setup_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert cache.setup_compilation_cache() == want  # same path every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_tp_share_keeps_widths():
    cfg = get_config("granite-3-2b")
    share = tp_share(cfg, 4)
    assert (share.n_heads, share.n_kv_heads, share.d_ff) == (8, 2, 2048)
    assert share.vocab_size == 12289
    assert (share.d_model, share.resolved_head_dim) == (2048, 64)
    assert tp_share(cfg, 1) is cfg
    with pytest.raises(ValueError):
        tp_share(cfg, 3)


def test_train_main_takes_argv_and_cuts_depth(no_cache):
    from repro.launch import train

    out = train.main(["--steps", "2", "--batch", "2", "--seq", "8", "--layers", "1",
                      "--tp-share", "2", "--requant-interval", "100"])
    reps = out["state"]["trainable"]["reps"]
    assert all(np.isfinite(rec["total"]) for rec in out["history"])
    # one layer of the reduced config's 2-way share: 2 of 4 query heads
    # of 16, 1 of 2 KV heads, 64 of 128 FFN columns, 256 of 512 vocab rows
    assert reps["blocks/p0/mixer/wq"]["wp"].shape[1:] == (1, 64, 32)
    assert reps["blocks/p0/mixer/wk"]["wp"].shape[1:] == (1, 64, 16)
    assert reps["blocks/p0/mlp/w_up"]["wp"].shape[1:] == (1, 64, 64)
    assert reps["embed"]["wp"].shape[1:] == (256, 64)


def test_serve_main_takes_argv_and_returns_engine(no_cache):
    from repro.launch import serve

    engine, results = serve.main([
        "--continuous", "--paged", "--paged-kernel", "--packed-bits", "4",
        "--slots", "2", "--requests", "3", "--prompt-len", "8", "--mixed-lens",
        "--max-new", "4", "--max-len", "32"])
    assert sorted(r.uid for r in results) == [0, 1, 2]
    assert all(len(r.tokens) == 4 for r in results)
    assert engine.scheduler.compiled_decode_programs() == 1
    # the decode program the scheduler serves, compiled for its operands
    assert "HloModule" in engine.scheduler.decode_program_text()


def test_chip_smoke_logit_programs_take_weights_as_arguments():
    """jit embeds closed-over arrays as constants; at published widths the
    weights copied into each of the smoke's programs exhaust host memory."""
    import importlib.util
    import re

    import jax.numpy as jnp

    from repro.configs import reduced_config
    from repro.models import init_packed_params, transformer

    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)

    cfg = reduced_config("granite-3-2b")
    params = init_packed_params(jax.random.PRNGKey(0), cfg, 6)
    cache = transformer.init_cache(cfg, 2, 64, jnp.dtype(cfg.kv_cache_dtype),
                                   paged_blocks=4, block_size=32)
    table = jnp.arange(4, dtype=jnp.int32).reshape(2, 2)
    chunk, step = chip_smoke.logit_programs(cfg, paged_kernel=True)
    texts = [
        chunk.lower(params, cache, jnp.zeros((2, 32), jnp.int32), jnp.zeros(2, jnp.int32),
                    jnp.full(2, 32, jnp.int32), table).as_text(),
        step.lower(params, cache, jnp.zeros((2, 1), jnp.int32), jnp.full(2, 32, jnp.int32),
                   jnp.ones(2, bool), table).as_text(),
    ]
    for text in texts:
        hex_consts = re.findall(r'dense<"0x([0-9A-F]*)"', text)
        assert max(map(len, hex_consts), default=0) < 2048
