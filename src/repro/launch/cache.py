"""JAX's persistent compilation cache, as the entry points turn it on.

The cache key includes its directory, so the directory must not move between
runs: it is ``JAX_COMPILATION_CACHE_DIR`` where that is set (JAX reads the
variable itself and nothing is set here), and otherwise the fixed
``.jax_cache`` directory at the root of the checkout, never one built from a
temp name, a pid or a time.  Importing this module changes nothing; entry
points call :func:`enable_compile_cache` from their ``main``.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
