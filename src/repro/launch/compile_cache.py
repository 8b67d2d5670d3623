"""Persistent XLA compilation cache, placed from outside the program.

When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module changes nothing.  Otherwise the cache lives at a fixed directory
inside the checkout (``.jax_cache/``, listed in ``.gitignore``): the
directory is part of what a later run must find again, so it is never
derived from a temporary name, a pid or the time.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
