"""Pallas TPU kernel: RWKV6 chunked linear-attention scan.

Schedule (DESIGN.md §4): grid = (B, H, nChunks); the chunk axis is LAST, so
TPU's sequential grid carries the (K, V) state matrix in VMEM scratch across
chunks — the inter-chunk recurrence never touches HBM. Per chunk:

    intra: (C,C) pairwise-decay attention (two MXU matmuls)
    inter: (C,K) @ (K,K) state read
    state: S <- diag(exp(cum_C)) S + k_carry^T @ v   (one MXU matmul)

Tiles: r/k/v/lw chunk tiles are (1, 1, C, K) with C=64, K=head_dim(64) —
(64, 64) MXU plane; the state scratch is (K, K) fp32. Working set ≈
4*C*K + K*K + C*C floats ≈ 100 KB — far under VMEM; larger C would
amortize better and is a recorded §Perf candidate.

Decay math is fp32 throughout; within-chunk cumulative log-decays are
bounded by C * |log w|, so exp() stays in range for the decays RWKV6
produces (w = exp(-exp(w0 + lora)), w0 ≈ -6 at init).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_out_ref,
                state_scr, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    r = r_ref[0, 0].astype(jnp.float32)        # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)           # (1, K)

    # Mosaic lowers neither cumsum nor row indexing: the chunk's prefix
    # sums and totals are small matmuls (HIGHEST keeps them fp32 sums)
    hp = jax.lax.Precision.HIGHEST
    t_pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (s_pos <= t_pos).astype(jnp.float32)
    cum = jax.lax.dot(tril, lw, precision=hp)   # (C, K) inclusive
    tot = jax.lax.dot(jnp.ones_like(tril), lw, precision=hp)  # rows = total
    state = state_scr[...]                     # (K, K)

    # inter-chunk: q_t reads the chunk-entry state with decay prod_{s<t} w
    q_in = r * jnp.exp(cum - lw)
    out_inter = jax.lax.dot(q_in, state)       # (C, K)

    # intra-chunk pairwise (strict lower triangle)
    kd = k * jnp.exp(-cum)
    att = jax.lax.dot_general(q_in, kd, (((1,), (1,)), ((), ())))  # (C, C)
    att = jnp.where(s_pos < t_pos, att, 0.0)
    out_intra = jax.lax.dot(att, v)

    # current-token bonus
    bonus = jnp.sum(r * u * k, axis=1, keepdims=True)
    out_bonus = bonus * v

    o_ref[0, 0] = (out_inter + out_intra + out_bonus).astype(o_ref.dtype)

    # state carry: S[i, j] decays by exp(total[i])
    k_carry = k * jnp.exp(tot - cum)
    decay = jax.lax.dot_general(lw, jnp.ones_like(lw),
                                (((0,), (0,)), ((), ())), precision=hp)
    new_state = (jnp.exp(decay) * state
                 + jax.lax.dot_general(k_carry, v, (((0,), (0,)), ((), ()))))
    state_scr[...] = new_state

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        s_out_ref[0, 0] = new_state


def wkv6_bhsk(r, k, v, log_w, u, *, chunk: int, interpret: bool):
    """r,k,v,log_w: (B,H,S,K) fp32; u: (H,K). Returns (out, final_state)."""
    b, h, s, dk = r.shape
    assert s % chunk == 0, f"S={s} must be a multiple of chunk={chunk}"
    n_chunks = s // chunk
    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    # (H, 1, K): a (1, K) block then spans the array's last two dims, as
    # the TPU lowering requires of a block that is not (8, 128)-aligned
    u3 = u.reshape(h, 1, dk)
    seq_spec = pl.BlockSpec((1, 1, chunk, dk),
                            lambda b_, h_, c: (b_, h_, c, 0))
    out, state = pl.pallas_call(
        kernel,
        grid=(b, h, n_chunks),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, dk), lambda b_, h_, c: (h_, 0, 0))],
        out_specs=[seq_spec,
                   pl.BlockSpec((1, 1, dk, dk),
                                lambda b_, h_, c: (b_, h_, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, dk), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, dk, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dk, dk), jnp.float32)],
        interpret=interpret,
    )(r, k, v, log_w, u3)
    return out, state
