"""JAX's persistent compilation cache for the launchers.

A full-width serving step takes tens of seconds to compile, and every
process starts with none.  The launchers call :func:`use_compile_cache`
before their first compile so that later processes find the compiled
programs again.  The directory is part of what a later process has to
find, so it is fixed: never derived from a temporary name, a process id
or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_compile_cache"]

# <checkout>/.jax_cache (listed in .gitignore)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is that directory: JAX reads
    the variable itself and nothing else is set here.  Otherwise the
    cache lives in :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
