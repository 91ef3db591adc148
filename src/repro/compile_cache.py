"""JAX's persistent compilation cache, placed from outside.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its cache there
and :func:`enable_compile_cache` changes nothing. Otherwise the cache
goes to ``<checkout>/.jax_cache``: a fixed path (the path is part of
what a cached entry is found by), listed in ``.gitignore``.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns the
    directory it lives in."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
