"""JAX's persistent compilation cache, placed for entry points.

Entry points (``launch/serve.py``, ``launch/train.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` once, before their first compile; nothing
calls it at import time. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
already reads it and no other directory is set. Otherwise the cache lives at
one fixed path inside the checkout: the path is part of a cache entry's key,
so it never comes from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return the directory in use."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
