"""The program under test, as the benchmark reaches it: its sources on
``sys.path`` (importing this module puts them there) and its compile
cache."""
from __future__ import annotations

import sys

from bench.lib.spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def use_compile_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed path in
    the checkout (or ``JAX_COMPILATION_CACHE_DIR``); every program is
    cached, however fast it compiled, so that set-up repeats."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
