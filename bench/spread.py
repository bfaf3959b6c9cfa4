#!/usr/bin/env python3
"""Spread of end-to-end metrics over two sets of runs of one cell.

    python3 bench/spread.py <set A result files...> -- <set B result files...>

Each file holds a run's standard output; its last line is the result.
For each metric, prints each set's spread (quartile distance over the
median, ``statistics.quantiles``), its median, and five times the wider
spread, the bound a cell measured so would call for (never under 1%).
Not part of a benchmark run.
"""
from __future__ import annotations

import json
import pathlib
import statistics
import sys

sys.path[:0] = [str(pathlib.Path(__file__).resolve().parent.parent)]

from bench import measure  # noqa: E402


def results(paths):
    return [json.loads(pathlib.Path(p).read_text().strip().splitlines()[-1]) for p in paths]


def main(argv) -> int:
    if "--" not in argv:
        raise SystemExit(__doc__)
    cut = argv.index("--")
    sets = [results(argv[:cut]), results(argv[cut + 1:])]
    names = sorted(set().union(*(r["metrics"] for s in sets for r in s)))
    for name in names:
        vals = [[r["metrics"][name]["value"] for r in s] for s in sets]
        spreads = [measure.spread(v) for v in vals]
        print(json.dumps({"metric": name, "medians": [statistics.median(v) for v in vals],
                          "spreads": spreads, "bound_5x": max(0.01, 5 * max(spreads)),
                          "values": vals}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
