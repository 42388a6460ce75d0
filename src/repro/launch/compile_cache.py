"""Persistent XLA compilation cache at a fixed, checkout-local path.

A TPU compile of a streaming scan or a model step takes seconds to minutes,
and every fresh process pays it again unless JAX's persistent cache is on.
The cache key includes the directory, so the path must not move between
runs: it is never built from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os

import jax

# <repo>/.jax_cache — this file is <repo>/src/repro/launch/compile_cache.py
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here. Otherwise the cache lives in :data:`CACHE_DIR`.
    Idempotent; call it before the first compile.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return jax.config.jax_compilation_cache_dir
