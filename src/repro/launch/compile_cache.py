"""JAX's persistent compilation cache for the command-line entry points.

A compiled program is keyed in part by the cache directory's path, so
the directory is fixed: ``.jax_cache/`` at the root of the checkout.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Libraries and tests never call this.
"""

from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; return it."""
    configured = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
