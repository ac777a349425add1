"""Placement of JAX's persistent compilation cache.

Entry points call :func:`enable_compile_cache` once at start, never at
import.  The cache directory is part of every entry's key, so it must not
move between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets
it (JAX reads that variable itself), else the repository's fixed
``.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Path:
    """Turn the persistent compilation cache on and return its directory.
    Every compile is cached, however short: serving compiles many small
    per-layer programs, each of which a cold process would otherwise pay
    again."""
    import jax

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        cache_dir = Path(env_dir)
    else:
        cache_dir = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def cache_entries(cache_dir: Path) -> int:
    """Number of compiled programs in a cache directory (0 if absent)."""
    if not cache_dir.is_dir():
        return 0
    return sum(1 for _ in cache_dir.glob("*-cache"))
