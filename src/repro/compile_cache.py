"""Persistent XLA compilation cache for the query path.

Fragment programs are compiled once per op tree × capacity bucket; the
cache lets a later process (a second session, the next smoke run on the
same machine) load them instead of compiling again.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, so the same checkout always finds its own entries again
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on for TPU programs; returns
    its directory (None where it stays off).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there
    and nothing is changed. Otherwise, on a TPU, the cache is the
    checkout's ``.jax_cache/``, and every program is cached however fast
    it compiled: fragment programs compile in well under JAX's default
    1 s floor. On other backends (the CPU test and interpret path) it
    stays off: XLA:CPU reloads its own entries with host-feature
    mismatch errors."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if jax.default_backend() != "tpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CHECKOUT_CACHE_DIR)
