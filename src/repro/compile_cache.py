"""JAX's persistent compilation cache, kept at a path that stays put.

A cache hit needs the same directory on the next run, so the directory is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
it itself), else ``.jax_cache`` at the root of this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the cache directory used when the environment names none (git-ignored)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile.  Returns the directory in use.  A directory set through
    ``JAX_COMPILATION_CACHE_DIR`` is left as it is."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
