"""Placement of jax's persistent compilation cache.

A cold process compiles every program it runs — on the chip that is a
minute and a half for the smoke's programs alone. jax can keep compiled
executables on disk, but only under a path that stays put: the directory
is part of what a later process must find again, so it is never built
from ``tempfile``, a pid or the clock.

Launchers (``chip_smoke.py``, ``bench.py``, the examples) call
:func:`enable_compile_cache` first thing.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# <checkout>/.jax_cache (git-ignored): the one place a run caches to when
# nobody placed the cache from outside
_REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it by itself
    and this function sets no other directory. Where it is not, the cache
    goes to ``.jax_cache`` at the root of this checkout.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    return str(_REPO_CACHE)
