"""The dense decoder family (Llama / Qwen2 form), as configuration files
with Hugging Face key names describe it.

Token embedding, then per layer: RMSNorm, grouped-query causal attention
with rotary position embedding, residual, RMSNorm, SwiGLU MLP, residual;
final RMSNorm and a head tied to the embedding. The reference is written
from the published description of these models, for one thing only: to be
the yardstick that decides ``correct``.

Canonical weights: a dict of arrays, per-layer weights stacked on a leading
layer axis. The program and the reference both get their weights from
``make`` (the program through ``pack_*``, which mirror the program's
parameter layouts), so the reference never reads an array the program
made.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.ops import DTYPES, cast, mm, rms, rope


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


def program_config(cfg: dict):
    """The program's ``ModelConfig`` of a decoder config file."""
    from bench.lib import program  # noqa: F401  (puts the program on sys.path)
    from repro.models.common import ModelConfig
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


def tiny(cfg: dict) -> dict:
    """Every width and count cut to a size a CPU test holds; grouped-query
    attention stays grouped."""
    return dict(cfg, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, head_dim=16, num_hidden_layers=2,
                vocab_size=256,
                num_key_value_heads=2 if cfg["num_key_value_heads"]
                < cfg["num_attention_heads"] else 4)


# -- weights and the program's layouts ------------------------------------

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


# -- the plain reference --------------------------------------------------

def _layer(m: dict, precision: str):
    dt = cast(precision)
    mm_ = lambda spec, a, b: mm(spec, a, b, precision).astype(dt)

    def body(x, w):
        b, s, _ = x.shape
        h = rms(x, w["ln1"].astype(dt), m["eps"])
        q = mm_("bsd,de->bse", h, w["wq"])
        k = mm_("bsd,de->bse", h, w["wk"])
        v = mm_("bsd,de->bse", h, w["wv"])
        if "bq" in w:
            q, k, v = (q + w["bq"].astype(dt), k + w["bk"].astype(dt),
                       v + w["bv"].astype(dt))
        q = rope(q.reshape(b, s, m["h"], m["hd"]), m["theta"])
        k = rope(k.reshape(b, s, m["hk"], m["hd"]), m["theta"])
        v = v.reshape(b, s, m["hk"], m["hd"])
        g = m["h"] // m["hk"]
        q = q.reshape(b, s, m["hk"], g, m["hd"])
        scores = mm_("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(
            jnp.asarray(m["hd"], dt))
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, jnp.asarray(-1e30, dt)
                           if dt == jnp.float32 else jnp.asarray(-3e38, dt))
        p = jax.nn.softmax(scores, axis=-1)
        att = mm_("bkgqs,bskd->bqkgd", p, v).reshape(b, s, m["h"] * m["hd"])
        x = x + mm_("bse,ed->bsd", att, w["wo"])
        h = rms(x, w["ln2"].astype(dt), m["eps"])
        up = mm_("bsd,df->bsf", h, w["wu"])
        gate = mm_("bsd,df->bsf", h, w["wg"])
        x = x + mm_("bsf,fd->bsd", jax.nn.silu(gate) * up, w["wd"])
        return x, None

    return body


def hidden(cfg: dict, weights: dict, tokens, *, precision="float32",
           remat: bool = False):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
    m = dims(cfg)
    dt = cast(precision)
    x = weights["embed"].astype(dt)[tokens]
    body = _layer(m, precision)
    if remat:
        body = jax.checkpoint(body)
    per_layer = {k: v for k, v in weights.items()
                 if k not in ("embed", "final_norm")}
    x, _ = jax.lax.scan(body, x, per_layer)
    return rms(x, weights["final_norm"].astype(dt), m["eps"])


def logits(cfg: dict, weights: dict, tokens, *, precision="float32"):
    """(B, S, vocab) logits; the head is the embedding, transposed."""
    x = hidden(cfg, weights, tokens, precision=precision)
    return mm("bsd,vd->bsv", x, weights["embed"], precision)


def loss(cfg: dict, weights: dict, tokens, labels, *, precision="float32"):
    """Mean next-token cross entropy over every position."""
    x = hidden(cfg, weights, tokens, precision=precision, remat=True)
    z = mm("bsd,vd->bsv", x, weights["embed"], precision)
    z = z.astype(cast(precision))
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


# -- counts that the per-layer metrics divide by --------------------------

def n_params(cfg: dict) -> int:
    """Every parameter, the tied head counted once."""
    total = 0
    for shape in shapes(cfg).values():
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


def _matmul_params(cfg: dict) -> int:
    """Weights that take part in a matrix product per token: the
    projections of every layer and the (tied) head; the embedding lookup,
    norms and biases are not products."""
    m = dims(cfg)
    q, kv = m["h"] * m["hd"], m["hk"] * m["hd"]
    per_layer = 2 * m["d"] * q + 2 * m["d"] * kv + 3 * m["d"] * m["f"]
    return m["L"] * per_layer + m["v"] * m["d"]


def model_flops_per_token(cfg: dict, seq_len: int) -> int:
    """Forward and backward FLOPs of one token: 6 per matrix-product
    weight plus 3 x the forward attention FLOPs against every position of
    the sequence (the score and the weighted value, 2 * 2 * heads *
    head_dim a layer). The program computes the whole S x S score matrix
    under a mask, so no causal halving is applied; recomputation is not
    counted."""
    m = dims(cfg)
    attn_per_key = 4 * m["L"] * m["h"] * m["hd"]
    return 6 * _matmul_params(cfg) + 3 * attn_per_key * seq_len


def kv_bytes_per_position(cfg: dict, bytes_per_value: int) -> int:
    """Keys and values of one position in every layer."""
    m = dims(cfg)
    return 2 * m["L"] * m["hk"] * m["hd"] * bytes_per_value
