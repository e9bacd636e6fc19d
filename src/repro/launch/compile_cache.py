"""JAX persistent compilation cache for the repo's entry points.

Scripts (`chip_smoke.py`, `examples/`, `benchmarks/`) call
`enable_compile_cache()` at start-up; importing the package never does,
so the test suite runs without a cache.

Where the cache lives:
  * `$JAX_COMPILATION_CACHE_DIR` when set — JAX reads the variable
    itself, and no other cache is set in code;
  * otherwise `<repo>/.jax_cache`, a fixed path (the path is part of
    what makes a later run find the entries, so it is never derived
    from a temporary name, a process id or the time).
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
