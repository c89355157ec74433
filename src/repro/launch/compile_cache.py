"""JAX's persistent compilation cache, placed for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call :func:`enable_compile_cache` first thing; importing the library
never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing else is set.  Otherwise the cache goes to one fixed
directory inside the checkout (``.jax_cache``, gitignored): the path is
part of the cache key, so a directory that moved between runs would
never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
