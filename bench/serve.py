"""Drive the program's serving path through one measured window.

The system under test is ``ServeEngine(continuous=True, paged=True,
paged_kernel=True)`` iterated through ``engine.stream(requests)``.  All
requests are handed over at once: the scheduler's queue is the backlog
of a closed loop with one client per lane, and a request is admitted
(sent) the step its client's previous one leaves its lane.

The window is kept by the flight recorder the engine is given: its
traces see every request event as the scheduler records it.  The window
opens at the event that meets the mix's condition and closes at the
first event past ``--seconds``; that event raises :class:`WindowClosed`
out of the stream, whose clean-up evicts the lanes.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import numpy as np


class WindowClosed(Exception):
    """Raised from the recorder at the first request event past the close."""


def make_recorder(capacity: int):
    """A FlightRecorder whose traces report each event to a hook."""
    from repro.obs import trace as obs_trace

    class HookedTrace(obs_trace.RequestTrace):
        __slots__ = ("hook",)

        def __init__(self, uid, hook):
            super().__init__(uid)
            self.hook = hook

        def event(self, kind, ts=None, **attrs):
            ev = super().event(kind, ts=ts, **attrs)
            self.hook(kind, ev.ts)
            return ev

    class WindowRecorder(obs_trace.FlightRecorder):
        def __init__(self):
            super().__init__(capacity=capacity)
            self.hook: Callable = lambda kind, ts: None

        def begin(self, uid, ts=None, **attrs):
            if uid in self.active:
                raise ValueError(f"request {uid!r} already has an open span")
            tr = HookedTrace(uid, lambda kind, t: self.hook(kind, t))
            tr.event(obs_trace.ENQUEUED, ts=ts, **attrs)
            self.active[uid] = tr
            self.begun_total += 1
            return tr

    return WindowRecorder()


@dataclasses.dataclass
class Window:
    """When the window opened and closed, and what was read at each end
    (``on_open`` returns its reading with the host time ``"t"`` it was
    taken at)."""
    seconds: float
    opens: Callable[[str], bool]
    on_open: Callable[[], dict]
    on_close: Callable[[], dict]
    t_open: Optional[float] = None
    t_close: Optional[float] = None
    at_open: Optional[dict] = None
    at_close: Optional[dict] = None

    def hook(self, kind: str, ts: float) -> None:
        from repro.obs import trace as obs_trace

        if self.t_close is not None or kind in obs_trace.TERMINAL:
            return
        if self.t_open is None:
            if self.opens(kind):
                # on_open may take time (starting a trace): the window
                # opens when it returns
                self.at_open = self.on_open()
                self.t_open = self.at_open["t"]
            return
        if ts >= self.t_open + self.seconds:
            self.t_close = self.t_open + self.seconds
            self.at_close = self.on_close()
            raise WindowClosed


def program_requests(reqs) -> list:
    from repro.serve import Request

    return [Request(uid=r.uid, tokens=r.prompt, max_new=r.max_new) for r in reqs]


def warm_requests(vocab: int, chunk_sizes) -> tuple:
    """Requests, one at a time, that take the engine through every program
    a window can run: for each chunk size, a prompt one token longer
    than the next smaller size, which that size alone covers (the chunk
    picked is the smallest covering the longest remaining prompt), then
    admission, decode steps and eviction.  Returns the requests and
    their arrival steps."""
    from repro.serve import Request

    rng = np.random.default_rng(0)
    sizes = sorted(set(int(c) for c in chunk_sizes))
    lens = [1] + [s + 1 for s in sizes[:-1]]
    reqs = [Request(uid=-1 - i, tokens=rng.integers(0, vocab, n, dtype=np.int32), max_new=3)
            for i, n in enumerate(lens)]
    return reqs, [8 * i for i in range(len(lens))]


def warm_table_updates(pool, max_chunk: int, workers: int = 8) -> int:
    """Compile the block-table update for every count of blocks one step
    can grant.  ``SlotPool.grow_many`` grants a step's blocks with one
    eager ``table.at[rows, cols].set(ids)``, whose shapes are the number
    of blocks granted: up to ``max_chunk / block_size`` (plus one for an
    unaligned start) per lane in a prefill chunk, one per lane in a
    decode step.  Each count is some nine small programs; they compile
    on ``workers`` threads, and the updates go to scratch copies.
    Returns the largest count warmed."""
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    n_slots, per_lane = pool.block_table.shape
    k_max = n_slots * (-(-max_chunk // pool.block_size) + 1)

    def update(k):
        rows = [i % n_slots for i in range(k)]
        cols = [i % per_lane for i in range(k)]
        return pool.block_table.at[jnp.asarray(rows), jnp.asarray(cols)].set(
            jnp.asarray(cols, jnp.int32))

    with ThreadPoolExecutor(workers) as ex:
        jax.block_until_ready(list(ex.map(update, range(1, k_max + 1))))
    return k_max


def events_by_request(recorder) -> Dict[int, list]:
    """uid -> [(kind, ts, attrs)] of every retained trace."""
    return {tr.uid: [(e.kind, e.ts, e.attrs or {}) for e in tr.events]
            for tr in recorder.traces()}


def run_window(engine, requests: list, window: Window) -> List:
    """Stream ``requests`` until the window closes; the finished Results."""
    engine.obs.recorder.hook = window.hook
    done = []
    try:
        for res in engine.stream(requests):
            done.append(res)
    except WindowClosed:
        pass
    finally:
        engine.obs.recorder.hook = lambda kind, ts: None
    if window.t_close is None:
        raise RuntimeError(
            "the mix ran out of requests before the window closed: raise its 'requests'")
    return done
