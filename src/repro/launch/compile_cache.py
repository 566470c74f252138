"""JAX's persistent compilation cache, placed from outside or at one fixed
path in the checkout.

Every CLI entry point calls :func:`enable_compile_cache` before its first
compile, so processes of one checkout share compiled programs across runs.
"""

from __future__ import annotations

import os
from pathlib import Path

# A fixed directory inside the checkout (listed in .gitignore): never built
# from a temporary name, a process id or the time, so a later run finds it.
REPO_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache goes to :data:`REPO_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
