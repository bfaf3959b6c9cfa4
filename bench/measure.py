"""End-to-end metrics from request events, and the spread of runs.

Events are ``(kind, ts, attrs)`` per request, on the host clock that the
program stamps them with: ``first_token`` after the chunk that completes
a prompt has been sampled on the host, ``decode_step`` after the step's
tokens reached the host.  Only events inside ``[t_open, t_close)`` count.
"""
from __future__ import annotations

import math
from statistics import quantiles
from typing import Dict, Iterable, List, Optional, Sequence

TOKEN_KINDS = ("first_token", "decode_step")


def percentile(values: Sequence[float], p: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def _inside(ts: float, t_open: float, t_close: float) -> bool:
    return t_open <= ts < t_close


def token_times(events: Dict[int, list]) -> Dict[int, List[float]]:
    """uid -> host times of its output tokens, in order."""
    return {uid: [ts for kind, ts, _ in evs if kind in TOKEN_KINDS]
            for uid, evs in events.items()}


def tokens_in_window(events, t_open, t_close) -> int:
    return sum(_inside(t, t_open, t_close)
               for ts in token_times(events).values() for t in ts)


def token_gaps(events, t_open, t_close) -> List[float]:
    """Seconds between a request's consecutive token events, both inside
    the window, over all requests.  A stall inside the window stays in
    full in every gap that spans it."""
    gaps = []
    for ts in token_times(events).values():
        for a, b in zip(ts, ts[1:]):
            if _inside(a, t_open, t_close) and _inside(b, t_open, t_close):
                gaps.append(b - a)
    return gaps


def ttfts(events, t_open, t_close) -> List[float]:
    """Seconds from ``admitted`` to ``first_token`` of each request whose
    first token falls in the window.  In a closed loop admission is the
    send."""
    out = []
    for evs in events.values():
        adm = next((ts for kind, ts, _ in evs if kind == "admitted"), None)
        first = next((ts for kind, ts, _ in evs if kind == "first_token"), None)
        if adm is not None and first is not None and _inside(first, t_open, t_close):
            out.append(first - adm)
    return out


def stalls(events, t_open, t_close, over: float = 1.5, same_step: float = 5e-3) -> dict:
    """Steps of the window, their median length, and the steps longer than
    ``over`` times it with the seconds they run beyond it.  A step is a
    run of token events less than ``same_step`` seconds apart: the host
    stamps a step's lanes one after another.  For standard error only."""
    ts = sorted(t for times in token_times(events).values() for t in times
                if _inside(t, t_open, t_close))
    starts = [t for i, t in enumerate(ts) if i == 0 or t - ts[i - 1] >= same_step]
    steps = [b - a for a, b in zip(starts, starts[1:])]
    if not steps:
        return {"steps": 0, "median_ms": None, "n": 0, "excess_s": 0.0}
    med = percentile(steps, 50)
    long = [d - med for d in steps if d > over * med]
    return {"steps": len(steps), "median_ms": 1e3 * med, "n": len(long), "excess_s": sum(long)}


def attempted(events, t_open, t_close) -> int:
    """Requests with any event inside the window."""
    return sum(any(_inside(ts, t_open, t_close) for _, ts, _ in evs)
               for evs in events.values())


def end_to_end(events, t_open: float, t_close: float) -> Dict[str, Optional[float]]:
    """Every end-to-end quantity a cell can report, with the sample counts
    and medians that go to standard error."""
    span = t_close - t_open
    gaps = token_gaps(events, t_open, t_close)
    ft = ttfts(events, t_open, t_close)
    ms = lambda xs, p: 1e3 * percentile(xs, p) if xs else None
    return {
        "output_tok_per_s": tokens_in_window(events, t_open, t_close) / span,
        "tpot_p95_ms": ms(gaps, 95), "tpot_p50_ms": ms(gaps, 50), "n_gaps": len(gaps),
        "ttft_p95_ms": ms(ft, 95), "ttft_p50_ms": ms(ft, 50), "n_ttft": len(ft),
    }


def spread(values: Iterable[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median (``statistics.quantiles``, exclusive method)."""
    xs = list(values)
    q1, q2, q3 = quantiles(xs, n=4)
    return (q3 - q1) / q2
