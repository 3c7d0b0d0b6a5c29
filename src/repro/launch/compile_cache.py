"""JAX's persistent compilation cache for this repo's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is configured here. Otherwise the cache lives at the fixed
``<repo>/.jax_cache`` (git-ignored): the path is part of what a run
finds again, so it never carries a temporary directory, a pid or a
timestamp. Entry points (``chip_smoke.py``, ``launch/serve_graphs.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` before their
first compile; library code never does.
"""
from __future__ import annotations

import os

import jax

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile: a cold chip run compiles
    # hundreds of small ones
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
