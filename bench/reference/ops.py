"""Arithmetic that the plain references of every model family share: the
stated dtypes, the precision of each product, RMSNorm and rotary position
embedding (rotate-half form).

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

# stated dtype of the configuration -> jnp dtype
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def cast(precision: str):
    """The dtype that values are held in at ``precision``."""
    return jnp.float32 if precision.startswith("float32") else jnp.bfloat16


def mm(spec: str, a, b, precision: str):
    """``jnp.einsum(spec, a, b)`` at ``precision``."""
    if precision.startswith("float32"):
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=jax.lax.Precision.HIGHEST
                          if precision == "float32" else None)
    if precision == "float8":
        a = a.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
        b = b.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))


def rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * scale


def rope(x, theta):
    """x: (B, S, H, D), positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]  # (S, D/2)
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
