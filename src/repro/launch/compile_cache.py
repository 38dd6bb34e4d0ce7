"""Persistent XLA compilation cache for the command-line entry points.

A cold serving run compiles one program per step bucket, about a minute
each at published widths; the persistent cache lets the next process
load them instead. The cache key includes the directory, so the default
is a fixed path inside the checkout (``<repo>/.jax_cache``, git-ignored),
never a temp, pid or time-stamped one.

Entry points call :func:`enable_compile_cache` first thing under their
``__main__`` guard. Importing this module changes nothing, and the test
suite never calls it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`DEFAULT_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
