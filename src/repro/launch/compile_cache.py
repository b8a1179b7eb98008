"""Persistent XLA compile cache for the entry points (serve, train, the chip
smoke script). Call ``enable_compile_cache()`` from a ``main``, never at
import: tests and library users keep JAX's own defaults."""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout (git-ignored): the cache directory is part of
# what a rerun must find again, so it never carries a pid, a temp name or
# a timestamp
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Keep compiled programs across runs and return where they go.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here. Otherwise the cache lives in ``.jax_cache`` at the
    root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
