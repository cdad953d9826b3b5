"""jit'd wrapper for the flash-attention kernel.

Public layout matches the model code: q (B, S, Hq, D); k/v (B, S, Hkv, D).
The wrapper transposes to (B, H, S, D) (head-major tiles so the kernel's
last two dims are the MXU-aligned (S, D) plane), pads S to a block multiple,
and picks block sizes; off-TPU it runs interpret=True.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.obs import flight as obs_flight
from repro.kernels.flash_attn import kernel

# Retuned for the skip-grid kernel (see kernel.py docstring): an
# asymmetric 256x128 tile measured fastest on the seq-1K bench shape.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 128
DEFAULT_BLOCK = DEFAULT_BLOCK_Q  # back-compat alias

# Mosaic's default scoped-VMEM limit on v5e.
VMEM_LIMIT = 16 * 1024 * 1024


def _vmem_bytes(hq: int, hkv: int, d: int, block_q: int, block_k: int,
                itemsize: int) -> int:
    """Upper estimate of what one grid step of the kernel keeps in VMEM.

    The last dim of every tile pads to whole 128-lane rows (D=64 costs
    128, the (.., 1) softmax stats cost 128). Double-buffered q, out, k
    and v tiles; fp32 acc, m and l scratch; and the body's fp32
    temporaries (q and k/v casts, logits, probabilities, mask).
    """
    lanes = -(-d // 128) * 128
    gbq = hq // hkv * block_q
    io = 2 * (2 * hq * block_q + 2 * hkv * block_k) * lanes * itemsize
    scratch = hkv * gbq * (lanes + 2 * 128) * 4
    temps = hkv * (gbq * lanes + 3 * gbq * max(block_k, 128)
                   + 2 * block_k * lanes) * 4
    return io + scratch + temps


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                   "block_q", "block_k", "skip"))
@obs_flight.kernel_annotation("flash_attn.forward")
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    skip: bool = True) -> jnp.ndarray:
    """skip=False keeps the full (q-block, k-block) grid (masking still
    applied in-kernel) — the non-skipping baseline the skip-grid kernel
    is bit-matched against in tests."""
    b, s, hq, d = q.shape
    block_q = min(block_q, max(8, 1 << (s - 1).bit_length()))
    block_k = min(block_k, block_q)
    # wide head groups or fp32 inputs: shorter q tiles until a step fits
    while block_q > block_k and _vmem_bytes(
            hq, k.shape[2], d, block_q, block_k,
            q.dtype.itemsize) > VMEM_LIMIT:
        block_q //= 2
    pad = (-s) % max(block_q, block_k)
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    if pad:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    out = kernel.flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, s_valid=s, skip=skip,
        interpret=_interpret())
    out = jnp.moveaxis(out, 1, 2)
    return out[:, :s] if pad else out
