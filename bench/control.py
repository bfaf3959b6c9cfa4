#!/usr/bin/env python3
"""Readings that set a cell's limit on ``max_logit_gap``: the program's
over many seeds (the lower reading) and the fp8 control's (the upper).

    python3 bench/control.py --workload <cell> --seconds <s> --seeds <n> [--control-seeds <m>]
                             [--fault <name>]

Not part of a benchmark run.  One process builds the cell's engine once
and, seed by seed, swaps in that seed's weights, serves the mix through
a window of the cell's own load exactly as ``bench/run.py`` does, and
reads the widest gap of the served tokens below the float32 reference's
best logit.  For the first ``--control-seeds`` seeds it also runs the
reference with every matmul operand in float8_e4m3fn at the same
positions and reads the gap of the token that control puts first.  With
``--fault``, one of ``bench/faults.py`` is planted in the program before
it is built, and the program's gap is read as the fault's.  One JSON
line per seed, then a summary line.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parent.parent)]

from bench import faults, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_000_000_000)
    ap.add_argument("--fault", choices=sorted(faults.FAULTS))
    args = ap.parse_args(argv)

    sys.path[:0] = [str(run.ROOT / "src")]
    _, _, _, config, mix = run.load_cell(args.workload)
    dev = run.start_jax().devices()[0]
    if dev.platform != "tpu":
        raise run.BenchError(f"JAX finds no TPU (device 0 is {dev.platform})")
    if args.fault:
        setattr(*faults.FAULTS[args.fault]())
    readings = control_readings(config, mix, dev, args.seconds,
                                [args.first_seed + i for i in range(args.seeds)],
                                args.control_seeds)
    lower = max(r["max_logit_gap"] for r in readings)
    upper = min((r["control_max_logit_gap"] for r in readings if "control_max_logit_gap" in r),
                default=None)
    print(json.dumps({"cell": args.workload, "fault": args.fault, "lower": lower,
                      "upper": upper, "device": dev.device_kind}), flush=True)
    return 0


def control_readings(config, mix, dev, seconds, seeds, control_seeds, log=print):
    """Per seed: the program's widest served-token gap and, for the first
    ``control_seeds`` seeds, the control's."""
    from bench import check, traffic, weights
    from repro.core.packing import PackedWeight

    watch = run.WindowWatch()
    engine, params, _ = run.build(config, mix, seeds[0], dev)
    template = jax_shapes(params)
    del params
    out = []
    for i, seed in enumerate(seeds):
        if i:
            engine.params = None
            engine.params = weights.program_params(
                seed, template, check.reference(config), config["model"], config["n_bits"],
                engine.cfg.pattern_len, PackedWeight)
        got = run.serve_window(engine, mix, seed, seconds, config["model"]["vocab"], watch)
        read = check.served_gaps(config, seed, got["prompts"], got["finished"],
                                 traffic.max_len(mix), control=i < control_seeds)
        read.update(seed=seed, bad=len(got["bad"]))
        log(json.dumps(read))
        out.append(read)
    return out


def jax_shapes(tree):
    import jax

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except run.BenchError as e:
        print(f"[control] error: {e}", file=sys.stderr)
        sys.exit(1)
