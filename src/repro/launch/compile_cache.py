"""JAX's persistent compilation cache for the command-line entry points.

A cold process compiles every jitted step program again (tens of seconds
for a full-width model); with the cache on, a repeat run loads them.
Entry points call :func:`enable_compile_cache` once, before their first
compile; importing the package never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed directory inside the checkout (ignored by git).  The cache's
#: location is part of how entries are found again, so it never depends
#: on a temporary name, a process id or the time.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    no other directory is set here; otherwise the cache is
    :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
