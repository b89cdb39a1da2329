"""Placement of jax's persistent compilation cache.

A cold process compiles every program it runs — on the chip that is a
minute and a half for the smoke's programs alone. jax can keep compiled
executables on disk, but only under a path that stays put: the directory
is part of what a later process must find again, so it is never built
from ``tempfile``, a pid or the clock.

Launchers (``chip_smoke.py``, ``benchmark/run.py``, the examples) call
:func:`enable_compile_cache` first thing.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# <checkout>/.jax_cache (git-ignored): the one place a run caches to when
# nobody placed the cache from outside
_ROOT = Path(__file__).resolve().parents[2]
_REPO_CACHE = _ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax reads it by itself
    and this function sets no other directory. Where it is not, the cache
    goes to ``.jax_cache`` at the root of this checkout. Either way the
    cache's key takes in a program's metadata (its ``op_name``s and source
    lines, the paths relative to this checkout).
    """
    # a cached executable carries the op_names and source lines of the
    # compile that wrote it. jax leaves them out of the key by default, so
    # a program whose scopes alone changed would load the old names and a
    # profiler trace would show those (core/instrument.py's ``scope``s are
    # what the benchmark's per-layer metrics read)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    # the source paths are part of that metadata: written relative to the
    # checkout, so a copy of it elsewhere still finds what this one cached
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        jax.config.update(
            "jax_hlo_source_file_canonicalization_regex",
            re.escape(str(_ROOT) + os.sep),
        )
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    return str(_REPO_CACHE)
