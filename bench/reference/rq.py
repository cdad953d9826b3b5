"""Randomized uniform quantization of one flat gradient message.

The buffer is cut into buckets of ``bucket_elems`` elements (the last one
shorter). Each bucket is coded on 2**bits - 1 levels spanning its own
[min, max]; an element rounds up with probability equal to its fractional
level, using uniforms drawn per bucket under fold_in(key, bucket) in the
shape (1, rows, 512) that covers the bucket, so the draws match the
message format element for element.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 512


def qdq(flat: jnp.ndarray, key, *, bits: int, bucket_elems: int):
    """Quantize and dequantize a (n,) float32 buffer."""
    n = flat.shape[0]
    levels = (1 << bits) - 1
    cap = -(-min(bucket_elems, n) // LANES) * LANES
    n_buckets = -(-n // cap)
    out = []
    for b in range(n_buckets):
        x = flat[b * cap: min(n, (b + 1) * cap)]
        rows = -(-x.shape[0] // LANES)
        u = jax.random.uniform(jax.random.fold_in(key, b), (1, rows, LANES),
                               jnp.float32).reshape(-1)[: x.shape[0]]
        lo, hi = jnp.min(x), jnp.max(x)
        scale = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
        norm = (x - lo) / scale
        fl = jnp.floor(norm)
        q = jnp.clip(fl + (u < norm - fl).astype(jnp.float32), 0.0, levels)
        out.append(q * scale + lo)
    return jnp.concatenate(out)
