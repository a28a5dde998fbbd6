"""JAX persistent compilation cache location, shared by every entry point.

The cache key includes the directory, so the path must not move between
runs: it is ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself), and otherwise the fixed ``.jax_cache/`` at the checkout
root.  Entry points call :func:`enable_compile_cache` from ``main``; nothing
calls it at import.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
