"""Where JAX keeps its persistent compile cache.

Entry points (launch/serve.py, launch/train.py, benchmarks/run.py,
chip_smoke.py) call `use_compile_cache()` before their first compile.
If `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and nothing is
changed. Otherwise the cache goes to one fixed, git-ignored directory in
the checkout: the path is part of the cache key, so it must not move
between runs. Nothing else in the code sets a cache directory.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the compile-cache directory in effect, setting the checkout's
    own when the environment names none."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
