"""JAX's persistent compilation cache, placed from outside the program.

Entry points (`serve`, `chip_smoke.py`, `benchmarks/run.py`) call
`use_compile_cache()` once before they compile anything; nothing calls it
at import time or in tests.  Where `JAX_COMPILATION_CACHE_DIR` is set,
JAX reads it itself and this sets no other path.  Otherwise the cache
lives at the fixed `<checkout>/.jax_cache` (git-ignored): the path is
part of the cache's key, so it must not move between runs.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
