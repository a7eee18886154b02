"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable` (``chip_smoke.py``,
``python -m repro.launch.serve``, ``python -m benchmarks.run``); nothing
calls it on import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and the location is left to it. Otherwise the cache goes to a fixed
``<repo>/.jax_cache`` (git-ignored): the path is part of what a later run
looks up, so it must not move between runs.

Every distinct prompt length compiles its own prefill per model (the engine
jits per shape). On a TPU v5e most of those compile in under a second, below
JAX's default one-second floor for caching, so the floor is dropped to zero:
a warm cache then serves every program a cold run compiled.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
