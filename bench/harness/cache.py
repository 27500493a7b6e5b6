"""JAX's persistent compilation cache at one fixed directory of the checkout.

The benchmark gives the program its cache directory rather than taking one
from the environment: ``.jax_compile_cache/`` at the checkout's root, the
same path on every run, so only a cell's first run in a checkout compiles
and nothing is shared with another checkout.  Eviction is off: a size cap
turns JAX's cache into a file-locked LRU that lists the whole directory on
each write, which serialises the set-up's compile threads on the lock.
"""

from __future__ import annotations

from pathlib import Path

import jax


def enable(root: Path) -> str:
    path = str(root / ".jax_compile_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    # keep every program, the sub-second ones of the request path too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
