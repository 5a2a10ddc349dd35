"""JAX's persistent compilation cache, set up in one place.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it as the default of
its ``jax_compilation_cache_dir`` option, and nothing here overrides it.
Where it is not set, the cache lives in :data:`CHECKOUT_CACHE_DIR`, one
fixed directory inside the checkout (listed in ``.gitignore``): the path is
part of what a cached entry is found by, so it must not move between runs.
Call :func:`enable_compile_cache` before the first compilation.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
