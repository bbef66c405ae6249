"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once at start-up (never at
import).  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the
cache from there and nothing is changed; otherwise the cache goes to one
fixed directory inside the checkout, so every later process started from the
same checkout finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — src/repro/compile_cache.py is three levels down
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
