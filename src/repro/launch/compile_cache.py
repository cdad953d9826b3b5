"""Where JAX keeps its persistent compilation cache.

Compiling the train step and the serve engine takes a large part of a cold
run on the chip; with the cache on, a second run in the same checkout loads
those programs instead. The entry points (``launch/train.py``,
``launch/serve.py``, ``chip_smoke.py``) call ``use_compile_cache()`` before
their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed, inside the checkout (listed in .gitignore): a cache that moves
# between runs is never hit
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache is ``.jax_cache`` at the root
    of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
