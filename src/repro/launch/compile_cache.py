"""JAX's persistent compilation cache at a fixed place.

The cache key includes the directory, so a path that moves between
runs (a temp dir, a pid, a timestamp) never hits.  Entry points call
``enable_compile_cache()`` before their first compile."""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself; nothing is set here), else ``.jax_cache/`` at the checkout
    root.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
