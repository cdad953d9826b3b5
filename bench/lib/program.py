"""The program under test, as the benchmark builds it from a
configuration file: its model configuration and its parameter layouts."""
from __future__ import annotations

import sys

from bench.lib.spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of a decoder config file."""
    from repro.models.common import ModelConfig
    from bench.reference.weights import dims
    if cfg.get("hidden_act") != "silu" or not cfg.get("tie_word_embeddings"):
        raise ValueError("only SwiGLU decoders with tied embeddings are "
                         "described by these config files")
    m = dims(cfg)
    return ModelConfig(
        arch_id=cfg["program_arch"], family="dense", n_layers=m["L"],
        d_model=m["d"], n_heads=m["h"], n_kv_heads=m["hk"], d_ff=m["f"],
        vocab=m["v"], head_dim=m["hd"], qkv_bias=m["bias"],
        rope_theta=m["theta"], norm="rmsnorm", norm_eps=m["eps"],
        act="silu", glu=True, tie_embeddings=True)


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
