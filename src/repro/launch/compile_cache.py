"""JAX persistent compilation cache location.

Entry points call :func:`enable_compile_cache` first (never at import):
a run on a fresh machine then reuses what an earlier run on the same
checkout compiled.  The directory is part of the cache key, so it is a
fixed path — ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself, and no other directory is set), else
``.jax_cache/`` at the checkout root (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: checkout root: src/repro/launch/compile_cache.py -> three levels up.
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """-> the cache directory in effect (see module docstring)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
