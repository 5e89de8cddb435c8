"""Where the entry points keep JAX's persistent compilation cache.

A compiled executable is found again only at the directory it was written
to, so the cache lives at one fixed place: ``JAX_COMPILATION_CACHE_DIR``
when it is set (JAX reads that variable itself), else ``.jax_cache/`` at
the root of this checkout.
"""
from __future__ import annotations

import os

import jax

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory and
    return that directory. Call before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
