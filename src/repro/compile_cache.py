"""JAX's persistent compilation cache at a place set from outside.

Call :func:`enable` from a program's ``main()``, never at import time.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
other directory is set here. Elsewhere the cache lives at a fixed
``.jax_cache/`` in the root of the checkout (git-ignored), so a later run
of the same checkout finds what an earlier one compiled.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "ENV_VAR", "enable"]

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: this file is <checkout>/src/repro/compile_cache.py
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Kernels compile in about a second, under JAX's default one-second
    floor for caching, so every compile is cached.
    """
    path = os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
