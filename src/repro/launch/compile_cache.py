"""JAX's persistent compilation cache for the entry points.

Entry points call :func:`use_compile_cache` from ``main``; importing a
library module never does, so tests run without a cache.  JAX reads
``JAX_COMPILATION_CACHE_DIR`` itself when it is set, and then nothing is
set here.  Otherwise the cache lives at ``.jax_cache/`` in the checkout:
a fixed path, because the path is part of what a cached entry matches.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
