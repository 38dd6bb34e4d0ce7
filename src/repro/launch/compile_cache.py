"""Persistent XLA compilation cache for the command-line entry points.

A cold serving run compiles one program per step bucket, about a minute
each at published widths; the persistent cache lets the next process
load them instead. The cache key includes the directory, so the default
is a fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored),
never a temp, pid or time-stamped one.

Entry points call :func:`enable_compile_cache` first thing under their
``__main__`` guard. Importing this module changes nothing, and the test
suite calls it only in processes of its own.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` names another directory, JAX
    already reads it and nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`, with no size cap. A cap meant for a shared
    directory (``JAX_COMPILATION_CACHE_MAX_SIZE``) would evict, least
    recently used first, a run's oldest programs once they outgrow it:
    the next process asks for them in the order they were written, so
    each miss's write evicts the entry it asks for next, and it loads
    none.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env and Path(env).resolve() != DEFAULT_DIR:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    return str(DEFAULT_DIR)
