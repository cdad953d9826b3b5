"""The benchmark's weights of a decoder configuration, made from the seed.

Canonical form: a dict of arrays, per-layer weights stacked on a leading
layer axis. The program and the reference both get their weights from
here (the program through ``pack_*``, which mirror the program's parameter
layouts), so the reference never reads an array the program made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# stated dtype of the configuration -> jnp dtype
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def dims(cfg: dict) -> dict:
    """Sizes of a decoder config file (Hugging Face key names)."""
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return {"d": d, "h": h, "hk": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or d // h,
            "f": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "v": cfg["vocab_size"], "theta": float(cfg["rope_theta"]),
            "eps": float(cfg["rms_norm_eps"]),
            "bias": bool(cfg.get("qkv_bias", False))}


def shapes(cfg: dict) -> dict:
    """name -> shape of every canonical weight, in a fixed order."""
    m = dims(cfg)
    d, L, q, kv, f = m["d"], m["L"], m["h"] * m["hd"], m["hk"] * m["hd"], \
        m["f"]
    out = {"embed": (m["v"], d), "final_norm": (d,),
           "ln1": (L, d), "ln2": (L, d),
           "wq": (L, d, q), "wk": (L, d, kv), "wv": (L, d, kv),
           "wo": (L, q, d), "wg": (L, d, f), "wu": (L, d, f),
           "wd": (L, f, d)}
    if m["bias"]:
        out.update({"bq": (L, q), "bk": (L, kv), "bv": (L, kv)})
    return out


def make(cfg: dict, key: jax.Array) -> dict:
    """Canonical weights from ``key``, rounded to the configuration's
    stated dtype and held in float32 (traced: call it inside jit)."""
    stated = DTYPES[cfg["torch_dtype"]]
    out = {}
    for i, (name, shape) in enumerate(shapes(cfg).items()):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if name == "embed":
            w = 0.02 * z
        elif name.startswith("w"):
            w = z / jnp.sqrt(jnp.float32(shape[-2]))
        elif name.startswith("b"):
            w = 0.02 * z
        else:                       # norm scales
            w = 1.0 + 0.1 * z
        out[name] = w.astype(stated).astype(jnp.float32)
    return out


def _block(c: dict, idx) -> dict:
    """One layer (idx an int) or the stack (idx a slice) of ``c`` in the
    program's block layout."""
    def lin(w, b=None):
        p = {"w": c[w][idx]}
        if b is not None and b in c:
            p["b"] = c[b][idx]
        return p
    return {"ln1": {"scale": c["ln1"][idx]},
            "mixer": {"q": lin("wq", "bq"), "k": lin("wk", "bk"),
                      "v": lin("wv", "bv"), "o": lin("wo")},
            "ln2": {"scale": c["ln2"][idx]},
            "ffn": {"gate": lin("wg"), "up": lin("wu"), "down": lin("wd")}}


def pack_unrolled(c: dict) -> dict:
    """The layout of ``repro.models.transformer`` (one dict per layer)."""
    L = c["ln1"].shape[0]
    return {"embed": c["embed"], "final_norm": {"scale": c["final_norm"]},
            "layers": [_block(c, i) for i in range(L)]}


def pack_scanned(c: dict) -> dict:
    """The layout of ``repro.models.transformer_scan`` (stacked layers)."""
    return {"embed": c["embed"], "final_norm": {"scale": c["final_norm"]},
            "prefix_layers": [], "scan_blocks": [_block(c, slice(None))],
            "suffix_layers": []}


def unpack_unrolled(tree: dict) -> dict:
    """Inverse of ``pack_unrolled`` (for gradients in that layout)."""
    layers = tree["layers"]
    stack = lambda f: jnp.stack([f(p) for p in layers])
    c = {"embed": tree["embed"], "final_norm": tree["final_norm"]["scale"],
         "ln1": stack(lambda p: p["ln1"]["scale"]),
         "ln2": stack(lambda p: p["ln2"]["scale"])}
    for name, (mod, sub) in {"wq": ("mixer", "q"), "wk": ("mixer", "k"),
                             "wv": ("mixer", "v"), "wo": ("mixer", "o"),
                             "wg": ("ffn", "gate"), "wu": ("ffn", "up"),
                             "wd": ("ffn", "down")}.items():
        c[name] = stack(lambda p: p[mod][sub]["w"])
        if "b" in layers[0][mod][sub]:
            c["b" + name[1]] = stack(lambda p: p[mod][sub]["b"])
    return c
