"""JAX's persistent compilation cache for the entry points that run on a
chip (``chip_smoke.py``, the benchmarks); the tests do not use it.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX
reads it itself, so nothing else is set in code), and otherwise a fixed
path inside the checkout: the path is part of the cache key, so it never
depends on a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")


def cache_dir() -> str:
    """Where compiled programs are kept."""
    return os.environ.get(ENV) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile of this process,
    however short; returns its directory."""
    import jax
    path = cache_dir()
    if not os.environ.get(ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
