"""Persistent XLA compilation cache for the entry points.

Called from each ``main`` (never at import), so library users and the tests
keep jax's own defaults.  ``JAX_COMPILATION_CACHE_DIR``, when set, wins:
jax reads it itself.  Otherwise the cache lives at a fixed path inside the
checkout; the path is part of the cache key, so it must not move between
runs.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_compilation_cache"))


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.  Calibration compiles
    ~150 sub-second programs, so every compile is kept, however short."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
