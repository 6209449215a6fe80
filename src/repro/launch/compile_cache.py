"""JAX's persistent compilation cache for the command-line entry points.

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()          # before the first compile

The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says when that variable
is set (JAX reads it itself; nothing else is configured here), and in
``<repo>/.jax_cache`` otherwise.  The path is fixed because it is part of
what a later process must find again.  Only entry points (``chip_smoke.py``,
the benchmark and example scripts, ``python -m repro.launch.serve``) call
this: importing the library or running the test suite leaves the cache off.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: The fallback cache directory: the repository root's ``.jax_cache``.
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile of this process and
    return its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # Keep every program: the engine's per-partition steps compile in well
    # under JAX's default one-second threshold, yet a cold run pays for
    # dozens of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
