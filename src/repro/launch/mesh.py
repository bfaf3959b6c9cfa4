"""Device mesh construction: the one constructor every launcher, bench
and test calls.

FUNCTIONS, not module-level constants: importing this module never
touches jax device state (required so smoke tests see 1 device while the
dry-run sees 512 host devices via XLA_FLAGS set before first jax init).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """A mesh whose axes are all ``Auto``: shardings are placed by the
    repo's PartitionSpecs and propagated by GSPMD.  ``jax.make_mesh``
    defaults to ``Explicit`` axes, under which array types carry their
    sharding and ops such as the embedding gather and the paged slot
    scatter are rejected (``DuplicateSpecError`` / ``ShardingTypeError``)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes), devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
