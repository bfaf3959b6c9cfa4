"""Placement of JAX's persistent compilation cache.

Called by the launchers and ``chip_smoke.py`` before their first
compile, never at import.  A published-width program takes tens of
seconds to compile for the chip; the cache lets later processes that
see the same directory load it instead.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache: fixed, because the directory is part of what a
# later run must find again (and it is listed in .gitignore).
_DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compilation_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it: the
    cache stays there and no other directory is set.  Otherwise the cache
    goes to ``<checkout>/.jax_cache``.  ``LIBTPU_INIT_ARGS`` is left as
    the machine sets it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_DIR))
    return str(_DEFAULT_DIR)
