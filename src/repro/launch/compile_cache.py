"""JAX persistent compilation cache for the repo's entry points.

Call :func:`enable_compile_cache` from a ``main()``, never at import, so tests
and library users keep JAX's defaults.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and this module sets no other directory.
Otherwise the cache lives in ``.jax_compile_cache/`` at the root of the
checkout, a path derived from this file: the same on every run, so later
runs from the same checkout find what earlier ones compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_compile_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to.

    The minimum compile time is lowered to zero so the one-to-two-second
    Pallas kernel compiles are kept too (JAX's default keeps only compiles
    of a second or more)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
