"""Where the entry points keep JAX's persistent compilation cache.

The cache key includes the directory, so it must not move between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads it
itself), else ``<repo>/.jax_cache`` (git-ignored).  Only entry points
call :func:`use_compile_cache`, before their first compile — importing
a library module never turns the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
