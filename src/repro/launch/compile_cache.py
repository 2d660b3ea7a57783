"""Where JAX keeps its persistent compilation cache.

Entry points (`chip_smoke.py`, `launch/serve.py`, `benchmarks/run.py`) call
`enable_compile_cache()` once at start-up, before their first compile; it
is never called at import time.
"""

from __future__ import annotations

import os
import pathlib

import jax

# fixed and inside the checkout (git-ignored): a run in the same place finds
# what the previous one compiled; a temp, pid- or time-derived path never hits
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this sets
    no other directory. Otherwise the cache goes to REPO_CACHE_DIR."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
