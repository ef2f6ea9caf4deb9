"""JAX's persistent compilation cache, turned on by the entry points.

Call :func:`enable_compile_cache` from a ``main()``, never at import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing more
is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``, a path
derived from this file's location: the directory is part of the cache key,
so it must not move between runs (no temporary name, pid or time in it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")  # .../src/repro/launch -> checkout
    jax.config.update("jax_compilation_cache_dir", path)
    return path
