"""JAX's persistent compilation cache for this repository's scripts.

``enable_compile_cache`` is called first thing by the entry-point scripts
(``chip_smoke.py``, ``benchmarks/bench_queries.py``), never at import time
and never by library code or tests.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX already reads it and no directory is set here.  Otherwise the cache
goes to ``<repo>/.jax_cache`` — a fixed path, never a temporary one.  Either
way every compile is cached, however short, so the short wave programs are
found again by the next process.
"""
from __future__ import annotations

import os
from pathlib import Path

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
