"""JAX's persistent compilation cache for the repo's entry points.

A later run finds a compiled program again only if it looks in the same
directory, so the directory never moves between runs:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it, else
``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's compilation cache at its directory and return it: the
    environment's ``JAX_COMPILATION_CACHE_DIR`` where set, otherwise the
    fixed ``.jax_cache`` inside the checkout.  Call it from an entry
    point's ``__main__``, never at import."""
    path = os.environ.get(ENV) or str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
