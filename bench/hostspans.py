"""The scheduler's spans in a profiler trace, and the device idle they cover.

The program wraps each iteration of its serving loop in a ``serve.step``
span and each phase of it in ``serve.<phase>``: ``admit``, ``blocks``,
``prefill``, ``decode``, ``sync``, ``finish``, with ``blocks`` and
``sync`` nested in ``prefill`` and ``decode``.  They are
``jax.profiler.TraceAnnotation``s on the trace's host plane, which
``devtrace.split`` puts on the device's clock.  A span's name is matched
up to any ``#`` that encodes its arguments.

The program also adds each span's seconds to its registry
(``serve_step_ms``, ``serve_host_seconds_total{phase}``), which
:func:`counters` reads at each end of the window.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from bench import devtrace

PREFIX = "serve."
STEP, SYNC = "serve.step", "serve.sync"
PHASES = ("admit", "blocks", "prefill", "decode", "sync", "finish")
OUTSIDE = "outside"  # idle while no step runs


def counters(registry) -> dict:
    """The program's step and phase instruments, for the window's ends:
    ``step_count``, ``step_ms_sum`` and ``host_s`` by phase (all 0 where
    the program records no spans)."""
    step = registry.histogram("serve_step_ms")
    host = registry.counter("serve_host_seconds_total", labels=("phase",))
    return {"step_count": step.count, "step_ms_sum": step.sum,
            "host_s": {p: host.labels(phase=p).value for p in PHASES}}


def base(name: str) -> str:
    return name.split("#", 1)[0]


def spans(host: list, t0: float, t1: float) -> Dict[str, List[Tuple[float, float]]]:
    """``serve.*`` spans by name, clipped to ``[t0, t1]``."""
    out: Dict[str, List[Tuple[float, float]]] = {}
    for name, s, e in host:
        n = base(name)
        if n.startswith(PREFIX) and e > t0 and s < t1:
            out.setdefault(n, []).append((max(s, t0), min(e, t1)))
    return out


def idle(device: dict, t0: float, t1: float) -> List[Tuple[float, float]]:
    """Intervals of ``[t0, t1]`` in which no ``XLA Ops`` event runs."""
    busy = devtrace.union([(max(s, t0), min(e, t1))
                           for _, s, e in device.get("XLA Ops", []) if e > t0 and s < t1])
    gaps, at = [], t0
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if at < t1:
        gaps.append((at, t1))
    return gaps


def split(gaps: List[Tuple[float, float]], named: Dict[str, list]) -> Tuple[Dict[str, float], float]:
    """Seconds of ``gaps`` under each innermost span (the phase's name;
    ``step`` for a step's own time, :data:`OUTSIDE` where no span runs),
    and the seconds inside a ``serve.step`` and outside any
    ``serve.sync``.  Spans of one name do not overlap; the innermost of
    nested spans is the one that started last."""
    marks = []
    for name, ivs in named.items():
        for s, e in ivs:
            key = (s, -e, name)
            marks.append((s, 1, key))
            marks.append((e, -1, key))
    for a, b in gaps:
        marks.append((a, 2, None))
        marks.append((b, -2, None))
    marks.sort(key=lambda m: m[0])
    by_span: Dict[str, float] = {}
    host_s = 0.0
    active: list = []
    in_gap = False
    prev = None
    for t, kind, key in marks:
        if in_gap and prev is not None and t > prev:
            names = {k[2] for k in active}
            label = base(max(active)[2])[len(PREFIX):] if active else OUTSIDE
            by_span[label] = by_span.get(label, 0.0) + (t - prev)
            if STEP in names and SYNC not in names:
                host_s += t - prev
        if kind == 1:
            active.append(key)
        elif kind == -1:
            active.remove(key)
        else:
            in_gap = kind == 2
        prev = t
    return by_span, host_s


def reduce(device: dict, host: list) -> dict:
    """Inside the benchmark's window (``devtrace.window``): the steps that
    ran, the device's idle seconds, the part of them the scheduler's
    own host work causes (inside a step and not waiting in a sync), and
    the idle split by innermost span, with the lines to log."""
    t0, t1 = devtrace.window(host)
    named = spans(host, t0, t1)
    gaps = idle(device, t0, t1)
    by_span, host_s = split(gaps, named)
    idle_s = sum(b - a for a, b in gaps)
    order = [p for p in PHASES + ("step", OUTSIDE) if p in by_span]
    return {
        "window_s": t1 - t0,
        "steps": len(named.get(STEP, [])),
        "idle_s": idle_s,
        "idle_host_s": host_s,
        "idle_by_span": by_span,
        "notes": [f"host spans: {len(named.get(STEP, []))} steps; device idle {idle_s:.6f} s "
                  f"of {t1 - t0:.6f} s, {host_s:.6f} s of it in the scheduler's host work; "
                  "by innermost span: "
                  + ", ".join(f"{p} {by_span[p]:.6f} s" for p in order)],
    }
