"""Persistent XLA compilation cache for the entry points.

Each entry point's ``main`` calls :func:`enable_compile_cache` before its
first compile; importing this module changes nothing.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the helper
leaves it alone.  Otherwise the cache goes to ``<checkout>/.jax_cache``: a
fixed path, because the directory is part of every entry's key, so a
temporary or per-run path would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout root: ``src/repro/utils`` -> three levels up
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Make sure JAX keeps compiled programs on disk; returns the directory."""
    import jax

    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
