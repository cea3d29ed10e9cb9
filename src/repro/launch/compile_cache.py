"""Where JAX keeps its persistent compilation cache.

The cache's path is part of its key, so it lives at one fixed place: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when that is set (JAX reads
the variable itself, and nothing here overrides it), otherwise
``<checkout>/.jax_cache``.  Entry points call :func:`use_compile_cache`
from ``main``; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
