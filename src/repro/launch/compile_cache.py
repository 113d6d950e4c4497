"""JAX's persistent compilation cache, placed from outside.

A cold run of a full-width expert bank spends most of its start-up in
XLA compiles; the persistent cache lets a later process skip them. A
later process finds them only where the earlier one wrote them, so the
directory never takes a temporary, per-process or dated name: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it; otherwise the cache lives at the fixed
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
