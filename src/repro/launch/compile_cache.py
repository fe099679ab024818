"""JAX's persistent compilation cache, set once by an entry point.

The directory is part of what a later run must find again, so it is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself), and ``<repo>/.jax_cache`` otherwise.  Entry points call
``enable_compile_cache()`` at start-up; importing this module changes
nothing.
"""
from __future__ import annotations

import os

REPO_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
