"""Persistent JAX compilation cache at a place that does not move.

The cache key includes the directory, so a path that changes between runs
(a temp dir, a pid or a timestamp in it) never hits. ``JAX_COMPILATION_CACHE_DIR``
places the cache from outside: JAX reads that variable itself, and then
nothing here overrides it. Otherwise the cache lives in ``.jax_cache/`` at
the repository root (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.
    Call before the first compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
