"""Where JAX keeps its persistent compile cache.

Entry points that run on a chip (`chip_smoke.py`, `benchmarks/run.py`)
call `enable()` once at start-up; importing the library never does.
Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and this
sets nothing else.  Otherwise the cache goes to one fixed directory in
the checkout, `.jax_cache/` (git-ignored): the path is part of the cache
key, so a directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
