"""Where compiled programs persist between processes."""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The default cache directory: fixed, because the path is part of what a
#: later process must find again.
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def place_compile_cache() -> str:
    """Give JAX's persistent compilation cache its directory and return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    Call it from an entry point, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
