"""JAX's persistent compilation cache, kept at one fixed place.

A cold process on the chip spends much of its start-up compiling.  The
cache key includes the directory, so a directory that moves never hits:
it is either what ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that
variable itself, and nothing here overrides it) or ``.jax_cache`` at the
root of this checkout.  Entry points call :func:`enable_compile_cache`
before their first compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
