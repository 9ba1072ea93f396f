"""Persistent XLA compilation cache shared by every launcher.

The cache key includes the directory, so the directory must not move
between runs: it is either what ``JAX_COMPILATION_CACHE_DIR`` names
(jax reads that variable itself) or the fixed ``<checkout>/.jax_cache``.
Call ``enable_compile_cache()`` before the first compile. Tests do not.
"""
from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (git-ignored); never a temp, pid or time path
DEFAULT_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
