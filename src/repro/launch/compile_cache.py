"""Where the launchers keep JAX's persistent compilation cache.

A set ``JAX_COMPILATION_CACHE_DIR`` is read by JAX itself and stands.
Otherwise the cache lives at ``.jax_cache/`` in the checkout: a fixed
path, so that a later run of the same checkout finds what an earlier run
compiled.  The tests never turn the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> Path:
    """Turn the persistent cache on, at ``$JAX_COMPILATION_CACHE_DIR`` or
    else the checkout's ``.jax_cache/``; call before the first compile.
    Returns the directory."""
    env = os.environ.get(_ENV)
    if env:
        return Path(env)
    path = CHECKOUT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return path
