"""Plain decoder-only transformer (Llama / Qwen2 form) in jax.numpy.

Token embedding, then per layer: RMSNorm, grouped-query causal attention
with rotary position embedding (rotate-half form), residual, RMSNorm,
SwiGLU MLP, residual; final RMSNorm and a head tied to the embedding.
Written from the published description of these models, for one thing
only: to be the yardstick that decides ``correct``.

``precision`` picks the arithmetic:
  "float32"   float32 everywhere, matrix products at HIGHEST (the reference)
  "float32_default"  float32 everywhere, matrix products at DEFAULT, the
              program's own setting (a witness for the correctness limits'
              readings, not a control)
  "bfloat16"  every value and product in bfloat16 (a control)
  "float8"    bfloat16, with both operands of every product rounded to
              float8_e4m3fn first (a control)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.weights import dims


def _cast(precision: str):
    return jnp.float32 if precision.startswith("float32") else jnp.bfloat16


def _mm(spec: str, a, b, precision: str):
    if precision.startswith("float32"):
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST
                          if precision == "float32" else None)
    if precision == "float8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale


def _rope(x, theta):
    """x: (B, S, H, D), positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]  # (S, D/2)
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(m: dict, precision: str):
    dt = _cast(precision)
    mm = lambda spec, a, b: _mm(spec, a, b, precision).astype(dt)

    def body(x, w):
        b, s, _ = x.shape
        h = _rms(x, w["ln1"].astype(dt), m["eps"])
        q = mm("bsd,de->bse", h, w["wq"])
        k = mm("bsd,de->bse", h, w["wk"])
        v = mm("bsd,de->bse", h, w["wv"])
        if "bq" in w:
            q, k, v = (q + w["bq"].astype(dt), k + w["bk"].astype(dt),
                       v + w["bv"].astype(dt))
        q = _rope(q.reshape(b, s, m["h"], m["hd"]), m["theta"])
        k = _rope(k.reshape(b, s, m["hk"], m["hd"]), m["theta"])
        v = v.reshape(b, s, m["hk"], m["hd"])
        g = m["h"] // m["hk"]
        q = q.reshape(b, s, m["hk"], g, m["hd"])
        scores = mm("bqkgd,bskd->bkgqs", q, k) / jnp.sqrt(
            jnp.asarray(m["hd"], dt))
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal, scores, jnp.asarray(-1e30, dt)
                           if dt == jnp.float32 else jnp.asarray(-3e38, dt))
        p = jax.nn.softmax(scores, axis=-1)
        att = mm("bkgqs,bskd->bqkgd", p, v).reshape(b, s, m["h"] * m["hd"])
        x = x + mm("bse,ed->bsd", att, w["wo"])
        h = _rms(x, w["ln2"].astype(dt), m["eps"])
        up = mm("bsd,df->bsf", h, w["wu"])
        gate = mm("bsd,df->bsf", h, w["wg"])
        x = x + mm("bsf,fd->bsd", jax.nn.silu(gate) * up, w["wd"])
        return x, None

    return body


def hidden(cfg: dict, weights: dict, tokens, *, precision="float32",
           remat: bool = False):
    """Final-normed hidden states (B, S, d) of ``tokens`` (B, S)."""
    m = dims(cfg)
    dt = _cast(precision)
    x = weights["embed"].astype(dt)[tokens]
    body = _layer(m, precision)
    if remat:
        body = jax.checkpoint(body)
    per_layer = {k: v for k, v in weights.items()
                 if k not in ("embed", "final_norm")}
    x, _ = jax.lax.scan(body, x, per_layer)
    return _rms(x, weights["final_norm"].astype(dt), m["eps"])


def logits(cfg: dict, weights: dict, tokens, *, precision="float32"):
    """(B, S, vocab) logits; the head is the embedding, transposed."""
    x = hidden(cfg, weights, tokens, precision=precision)
    return _mm("bsd,vd->bsv", x, weights["embed"], precision)


def loss(cfg: dict, weights: dict, tokens, labels, *, precision="float32"):
    """Mean next-token cross entropy over every position."""
    x = hidden(cfg, weights, tokens, precision=precision, remat=True)
    z = _mm("bsd,vd->bsv", x, weights["embed"], precision)
    z = z.astype(_cast(precision))
    lse = jax.nn.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)
