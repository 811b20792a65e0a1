"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`setup_compile_cache` once, before they compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
sets nothing, so the cache can be placed from outside. Otherwise the
cache lives at ``.jax_cache/`` in the checkout root: a fixed path,
because the path is part of every entry's key and a directory that
moves never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
