"""Faults planted underneath the timed path, to show that ``correct``
catches them.  Not part of a benchmark run: ``bench/control.py --fault``
reads them on the chip at a cell's own size, and the harness's tests at
a small size on the CPU.

Each fault is ``name -> () -> (owner, attribute, replacement)``; planting
it is ``setattr(owner, attribute, replacement)`` before the engine is
built, so the programs are traced with it.
"""
from __future__ import annotations


def stale_cache():
    """A decode step that returns the KV cache it was given."""
    from repro.models import transformer

    step = transformer.decode_step

    def stale(params, cache, *a, **k):
        logits, _ = step(params, cache, *a, **k)
        return logits, cache

    return transformer, "decode_step", stale


def altered_token():
    """One sampled token in about seven moved to its neighbour."""
    from repro.serve.engine import ServeEngine

    sample = ServeEngine._sample

    def altered(self, logits, temperatures, any_hot):
        tok = sample(self, logits, temperatures, any_hot)
        return (tok + (tok % 7 == 0)) % self.cfg.vocab_size

    return ServeEngine, "_sample", altered


FAULTS = {"stale-cache": stale_cache, "altered-token": altered_token}
