"""Where JAX keeps its persistent compilation cache.

A run finds what an earlier run compiled only if both use one directory:
`JAX_COMPILATION_CACHE_DIR`, when set, is used as it is (JAX reads it
itself); otherwise the cache lives in `.jax_cache/` at the repo root. Entry
points call `setup_compile_cache()` before their first compile.

Every executable is cached, however fast it compiled: a serving run builds
many sub-second ones, and their trace-and-compile adds up. JAX never caches
an executable that holds a host callback (the `base` variants' graph
gathers), so those compile on every run.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; return it."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
