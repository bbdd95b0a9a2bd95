"""JAX's persistent compilation cache, kept at one fixed path.

A path that moves between runs (a temp name, a PID, a timestamp) never
finds what an earlier run wrote, so the path is fixed. Entry points call
:func:`enable_compile_cache` once, before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# <repo>/.jax_cache (this file is <repo>/src/repro/launch/compile_cache.py)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
