"""Where JAX keeps its persistent compile cache for the repo's scripts.

`chip_smoke.py` and `benchmarks/run.py` call `use_compile_cache()` before
they compile anything. The library itself never sets a cache, and neither
do the tests.
"""
from __future__ import annotations

import os
from pathlib import Path

#: the fixed fallback: `.jax_cache/` at the root of the checkout (gitignored)
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    If JAX_COMPILATION_CACHE_DIR is set, JAX reads it by itself and nothing
    is set here. Otherwise the cache goes to `REPO_CACHE_DIR`, which never
    moves between runs (a cache whose path changes never hits)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
