#!/usr/bin/env python3
"""Compile every program a cell runs for a described TPU v5e, with no chip,
and print each program's ``memory_analysis()``.

    JAX_PLATFORMS=cpu python3 bench/aot.py [<cell> ...]

The programs are the serving path's decode step and its prefill chunk at
each chunk size, traced as the scheduler traces them, at the cell's lane
count, maximum length and KV pool.  Neither program donates the pool, so
its bytes are counted in the arguments and again in the outputs.  Not
part of a benchmark run: a check to make before a cell goes to the chip.
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import sys
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parent.parent


def programs(cfg, n_slots: int, max_len: int, n_blocks: int, block: int, chunk_sizes):
    import jax.numpy as jnp

    from repro.models import transformer

    cache_dtype = jnp.dtype(cfg.kv_cache_dtype)

    def decode(p, cache, tok, pos, act, table):
        return transformer.decode_step(p, cache, tok, pos, cfg, active=act, block_table=table,
                                       paged_kernel=True)

    def chunk(p, cache, toks, start, nvalid, table):
        return transformer.prefill_chunk(p, cache, toks, start, nvalid, cfg,
                                         cache_dtype=cache_dtype, block_table=table)

    lanes = (n_slots,)
    table = (n_slots, math.ceil(max_len / block))
    yield "decode", decode, [((n_slots, 1), "int32"), (lanes, "int32"), (lanes, "bool"),
                             (table, "int32")]
    for c in chunk_sizes:
        yield f"chunk{c}", chunk, [((n_slots, c), "int32"), (lanes, "int32"), (lanes, "int32"),
                                   (table, "int32")]


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import traffic
    from bench.run import program_config
    from repro.kernels import ops
    from repro.models import init_packed_params, transformer
    from repro.serve.scheduler import SchedulerPolicy

    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = argv if argv else [w["name"] for w in bench["workloads"]]
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    place = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    chunk_sizes = SchedulerPolicy().chunk_sizes
    for name in cells:
        cell = {w["name"]: w for w in bench["workloads"]}[name]
        entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
        config = json.loads((ROOT / entry["file"]).read_text())
        mix = json.loads((ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
        cfg = program_config(config)
        n_slots, max_len, block = mix["clients"], traffic.max_len(mix), 32
        n_blocks = n_slots * math.ceil(max_len / block)
        params = place(jax.eval_shape(lambda: init_packed_params(
            jax.random.PRNGKey(0), cfg, config["n_bits"])))
        cache = place(jax.eval_shape(lambda: transformer.init_cache(
            cfg, n_slots, max_len, jnp.dtype(cfg.kv_cache_dtype), paged_blocks=n_blocks,
            block_size=block)))
        for prog, fn, shapes in programs(cfg, n_slots, max_len, n_blocks, block, chunk_sizes):
            args = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one) for s, d in shapes]
            with mock.patch.object(ops, "_on_tpu", return_value=True):
                compiled = jax.jit(fn).lower(params, cache, *args).compile()
            ma = compiled.memory_analysis()
            total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
            print(json.dumps({"cell": name, "program": prog, "n_slots": n_slots,
                              "max_len": max_len, "n_blocks": n_blocks,
                              "argument_bytes": ma.argument_size_in_bytes,
                              "output_bytes": ma.output_size_in_bytes,
                              "alias_bytes": ma.alias_size_in_bytes,
                              "temp_bytes": ma.temp_size_in_bytes, "total_bytes": total}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
