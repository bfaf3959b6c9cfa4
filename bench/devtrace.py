"""Reduction of a JAX profiler trace to device times.

The trace's device plane (``/device:TPU:0``) has a line ``XLA Modules``
(one event per program execution, named ``jit_<function>(<id>)``) and a
line ``XLA Ops`` (one event per HLO instruction execution, named by the
instruction's text: ``%bitserial_matmul_pallas.67 = bf16[16,8192]...
custom-call(bf16[16,2048] ..., u8[6,256,8192] ..., ...)``).  A Pallas
kernel's instruction is named after its ``pallas_call`` function, and its
operand shapes are in the name, so each call's operations and bytes are
computed from that one event.  Host planes carry the runtime's spans
(execute, transfer, compile), which label the device's idle gaps.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

# Programs of the serving path, by the jitted function's name.
PROGRAMS = {"decode": "jit__decode_fn(", "chunk": "jit_chunk_into_pool("}
# Kernels, by the name their pallas_call gives the HLO instruction.
KERNELS = {"bitserial": "%bitserial_matmul_pallas", "paged_attention": "%paged_attention_pallas"}
TOP = 10
# Host spans the benchmark writes into the trace at the window's ends.
OPEN_MARK, CLOSE_MARK = "bench_window_open", "bench_window_close"

_CONTROL = re.compile(r"\) (while|conditional|call)\(|^%(while|conditional)")
_SHAPE = re.compile(r"(bf16|f32|u8|s32|pred|f16|s8|u32)\[([0-9,]*)\]")


def shapes(op_name: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of the result then each operand, in the order the
    instruction text lists them (layouts ``{...}`` are skipped)."""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(op_name)]


def bitserial_shape(op_name: str) -> Tuple[int, int, int, int]:
    """(M, K, N, n_bits) of a bitserial call from its instruction text:
    result (M, N), x (M, K), planes (n_bits, K/8, N)."""
    s = shapes(op_name)
    (_, (m, n)), (_, (_, k)), (_, (n_bits, _, _)) = s[0], s[1], s[2]
    return m, k, n, n_bits


def short(op_name: str) -> str:
    """Instruction name and result shape: ``%name.7 = bf16[16,8192]``."""
    head, _, rest = op_name.partition(" = ")
    return f"{head} {rest.split('{')[0].split(' ')[0]}" if rest else head


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def load(trace_dir: str):
    """The ``ProfileData`` of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"want one .xplane.pb under {trace_dir}, found {len(paths)}")
    return ProfileData.from_file(paths[0])


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def split(profile) -> Tuple[dict, list]:
    """({device line name: events}, host events) with events as
    ``(name, start_s, end_s)`` on the profile's common clock."""
    device, host = None, []
    for plane in profile.planes:
        if plane.name.startswith("/device:TPU:"):
            if device is None:  # the one chip a cell drives
                device = {ln.name: _events(ln) for ln in plane.lines}
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend(_events(ln))
    if device is None:
        raise RuntimeError("the trace has no TPU device plane")
    return device, host


def label(gap: Tuple[float, float], host: list, t0: float, t1: float) -> str:
    """The host span that overlaps the gap most (the shortest on a tie),
    leaving out spans that cover the whole window ``[t0, t1]``."""
    a, b = gap
    best = None
    for name, s, e in host:
        if s <= t0 and e >= t1:
            continue
        ov = min(b, e) - max(a, s)
        if ov > 0:
            key = (ov, -(e - s))
            if best is None or key > best[0]:
                best = (key, name)
    return best[1][:120] if best else "no host span"


def window(host: list) -> Tuple[float, float]:
    """(open, close) on the trace's clock, from the benchmark's marks."""
    opens = [e for name, _, e in host if name == OPEN_MARK]
    closes = [s for name, s, _ in host if name == CLOSE_MARK]
    if len(opens) != 1 or len(closes) != 1:
        raise RuntimeError(f"want one {OPEN_MARK} and one {CLOSE_MARK} in the trace, "
                           f"found {len(opens)} and {len(closes)}")
    return opens[0], closes[0]


def reduce_events(device: dict, host: list) -> dict:
    """Device busy time, per-program and per-kernel device time, and the
    breakdown of the most costly operations and the longest idle gaps,
    all inside the window the marks bound.  An event that straddles an
    end counts for its part inside."""
    t0, t1 = window(host)

    def clip(events):
        return [(n, max(s, t0), min(e, t1)) for n, s, e in events if e > t0 and s < t1]

    ops = clip(device.get("XLA Ops", []))
    modules = clip(device.get("XLA Modules", []))
    window_s = t1 - t0
    busy = union([(s, e) for _, s, e in ops])
    busy_s = sum(e - s for s, e in busy)
    programs: Dict[str, dict] = {k: {"n": 0, "device_s": 0.0} for k in PROGRAMS}
    spans = []  # (start, end, program) of each execution of a known program
    for name, s, e in modules:
        for prog, prefix in PROGRAMS.items():
            if name.startswith(prefix):
                programs[prog]["n"] += 1
                programs[prog]["device_s"] += e - s
                spans.append((s, e, prog))
    spans.sort()
    kernels: Dict[Tuple[str, str], dict] = {}
    per_op: Dict[str, float] = {}
    j = 0
    for name, s, e in sorted(ops, key=lambda x: x[1]):
        while j < len(spans) and spans[j][1] < s:
            j += 1
        prog = spans[j][2] if j < len(spans) and spans[j][0] <= s <= spans[j][1] else "other"
        if not _CONTROL.search(name):  # a loop's time is its body's
            key = f"{prog}:{short(name)}"
            per_op[key] = per_op.get(key, 0.0) + (e - s)
        for kern, prefix in KERNELS.items():
            if name.startswith(prefix):
                k = kernels.setdefault((prog, kern), {"n": 0, "device_s": 0.0, "calls": {}})
                k["n"] += 1
                k["device_s"] += e - s
                k["calls"][name] = k["calls"].get(name, 0) + 1
    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "programs": programs,
        "kernels": kernels,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
            "idle_gaps": [[label(g, host, t0, t1), g[1] - g[0]] for g in gaps],
        },
        "notes": [f"trace: {p} program ran {v['n']} times, {v['device_s']:.6f} s on the device"
                  for p, v in programs.items()]
                 + [f"trace: {k} calls in {p}: {v['n']}, {v['device_s']:.6f} s"
                    for (p, k), v in kernels.items()],
    }

