"""Persistent XLA compilation cache for the entry points.

A cold start compiles every step program; at full width that is most of
a short run. The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when
the environment sets it (JAX reads the variable itself, so nothing is set
here), and otherwise at the fixed ``<checkout>/.jax_cache``: the cache key
includes the path, so the directory must not move between runs. Tests do
not call this — they compile for described devices, whose entries cannot
be read back."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
