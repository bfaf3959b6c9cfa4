"""One general generator of closed-loop traffic from a mix's data file.

A mix (``bench/traffic/<name>.json``) gives the number of clients, the
prompt- and output-length distributions and how many requests to make.
Lengths are a fixed stratified set, the
distribution's quantiles at ``(j + 1/2) / n``, sent in one fixed
shuffled order; the seed draws the prompt tokens (and, in
``bench/weights.py``, the weights).  So every seed offers the same work
in the same order, and runs differ by their data and by noise alone: the
program's work depends on lengths and lane timing, never on token
values.

The loop starts mid-flight: the clients' first requests take the output
lengths that a loop which has run for a long time has left in its lanes
(:func:`residual`), so lanes finish and refill at staggered steps from
the first, as in steady state.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

KEYS = {"loop", "clients", "requests", "prompt", "output", "why", "source"}
RESIDUAL_POOL = 1000  # stratified lengths the residual draw is taken over


@dataclasses.dataclass(frozen=True)
class Req:
    uid: int
    prompt: np.ndarray  # (S,) int32
    max_new: int


def quantile(dist: dict, u: float) -> int:
    """Length at quantile ``u`` of a ``lognormal`` (median, sigma) or
    ``uniform`` distribution, clipped to [min, max]."""
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif dist["dist"] == "uniform":
        v = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return int(min(max(round(v), lo), hi))


def stratified(dist: dict, n: int) -> List[int]:
    return [quantile(dist, (j + 0.5) / n) for j in range(n)]


def residual(dist: dict, n: int) -> List[int]:
    """``n`` stratified remaining output lengths of the requests a closed
    loop has in flight after a long run: a request is met in proportion
    to its length, at a uniform point of it, so a remaining length ``r``
    has weight ``P(length >= r)``."""
    rem = np.sort(np.concatenate([np.arange(1, n_out + 1)
                                  for n_out in stratified(dist, RESIDUAL_POOL)]))
    return [int(rem[int((j + 0.5) / n * len(rem))]) for j in range(n)]


def validate(mix: dict) -> None:
    missing = KEYS - set(mix) - {"why", "source"}
    extra = set(mix) - KEYS
    if missing or extra:
        raise ValueError(f"traffic mix: missing keys {sorted(missing)}, unknown {sorted(extra)}")
    if mix["loop"] != "closed":
        raise ValueError("only closed-loop mixes exist: the program has no wall-clock submit API")
    if mix["requests"] < mix["clients"]:
        raise ValueError("a mix needs at least one request per client")


def max_len(mix: dict) -> int:
    """Longest sequence a lane can hold: longest prompt plus longest output."""
    return mix["prompt"]["max"] + mix["output"]["max"]


ORDER_SEED = 0  # the one shuffle of lengths every seed shares


def generate(mix: dict, seed: int, vocab: int) -> List[Req]:
    """The mix's requests, in the order the clients send them: the first
    ``clients`` are a stratified set of their own (the lanes' first
    requests, with residual outputs), the rest another."""
    validate(mix)
    c, n = mix["clients"], mix["requests"]
    order = np.random.default_rng(ORDER_SEED)
    sizes = []
    for part, first in ((c, True), (n - c, False)):
        if not part:
            continue
        outs = residual(mix["output"], part) if first else stratified(mix["output"], part)
        p = order.permutation(stratified(mix["prompt"], part))
        o = order.permutation(outs)
        sizes += list(zip(p, o))
    rng = np.random.default_rng(seed)
    return [Req(uid=i, prompt=rng.integers(0, vocab, int(p), dtype=np.int32), max_new=int(o))
            for i, (p, o) in enumerate(sizes)]
