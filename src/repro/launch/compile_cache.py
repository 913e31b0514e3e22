"""Where JAX keeps its persistent compilation cache.

Call ``use_compilation_cache()`` once, before the first JAX computation
of an entry point.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here.  Otherwise the cache goes to
``.jax_cache/`` at the root of this checkout: a fixed path, because the
path is part of the cache key and a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compilation_cache"]

# src/repro/launch/compile_cache.py -> the checkout root is three levels up
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compilation_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
