"""JAX's persistent compilation cache at one fixed place per checkout.

Entry points call :func:`enable_compile_cache` from their ``main()``; no
module calls it at import.  The cache directory is part of what a cached
executable is found by, so it must not move between runs: it is either the
directory ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable
itself, and this function then sets nothing) or ``<repo root>/.jax_cache``.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
