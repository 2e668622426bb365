"""Where an entry point keeps JAX's persistent compilation cache.

Importing the library sets no cache; entry points (``chip_smoke.py``,
``chipbench/run.py``) call :func:`use_compile_cache` once, before their
first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def use_compile_cache(checkout: str | os.PathLike) -> str:
    """Keep the cache where ``JAX_COMPILATION_CACHE_DIR`` says — JAX reads
    that variable itself, so nothing is set — and otherwise at
    ``<checkout>/.jax_cache``. The directory is fixed, never derived from
    a temp name, a pid or the time: a cache whose path moves never hits.
    Returns the directory in use."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    path = str(Path(checkout).resolve() / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
