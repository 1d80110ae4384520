"""Where JAX keeps its persistent compilation cache.

A directory path is part of the cache's key, so the cache only hits
again if every run uses the same one.  ``JAX_COMPILATION_CACHE_DIR``
wins when it is set; otherwise the cache lives in ``.jax_cache`` at the
root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_
    CACHE_DIR`` if set, else at the checkout's ``.jax_cache``, and return
    that directory.  Call it before the first compile of a process."""
    path = os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
