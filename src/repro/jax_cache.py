"""Where JAX keeps its persistent compilation cache.

The cache directory is placed from outside the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing here
changes any setting.  Otherwise the cache lives at one fixed directory inside
the checkout (``.jax_cache/``, gitignored).  The path is part of what a later
run must find, so it is never built from a temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call before the first compile.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    path = str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
