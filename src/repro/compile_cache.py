"""Persistent compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, ``examples/``) call
:func:`enable_compile_cache` before their first compile.  Library modules
and tests never do.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets no other directory.  Otherwise the cache lives at the fixed
path ``<checkout>/.jax_cache``: the directory is part of what a later run
must find, so it is never built from a temporary name, a process id or
the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    path = os.environ.get(CACHE_ENV) or str(CHECKOUT / ".jax_cache")
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    # Cache every executable: the Pallas kernels compile in about a second
    # each, under JAX's default one-second threshold.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
