"""Where JAX keeps its persistent compilation cache.

A chip run compiles from cold unless an earlier process left its programs
behind.  The cache key includes the directory, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
itself), and otherwise the cache lives at ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_CACHE = os.path.normpath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compilation."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE
        jax.config.update("jax_compilation_cache_dir", path)
    return path
