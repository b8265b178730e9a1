"""Persistent compilation cache for the entry points.

A cold 32-layer block step compiles for about a minute on the chip; the
cache makes every later process that runs the same program skip that.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "CACHE_DIR"]

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set: a
#: fixed path inside the checkout, never a temporary name, so the next run
#: from the same checkout finds it
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here; otherwise the cache goes to :data:`CACHE_DIR`.
    Call before the first compilation.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
