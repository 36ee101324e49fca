"""JAX persistent compilation cache for the device entry points.

Each bucketed wavefront shape compiles once per process.  The persistent cache
lets a later process on the same machine load those programs instead of
compiling them again.  :func:`enable_compile_cache` is called by the entry
points that drive the chip (``chip_smoke.py``, ``repro.launch.serve``), never
at import time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

#: ``<checkout>/.jax_cache``: a fixed path, since the cache keys on it.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    directory is set here; otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`.  The minimum compile time is lowered to zero,
    so the second-scale wavefront programs are cached too.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
