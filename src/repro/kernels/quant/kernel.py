"""Pallas TPU kernels: fused stochastic quantize-dequantize (Eq. 3.1) and
the packed wire-format encode/decode pair.

Layout/tiling rationale (TPU v5e):
  * the array is viewed as (R, C) with C a multiple of 128 (lane width);
    the wrapper pads/reshapes arbitrary tensors into this layout;
  * grid over row-tiles; BLOCK_R is chosen in ops.py per kernel from the
    actual resident operand dtypes so VMEM stays under budget;
  * (lo, scale) arrive as a whole (1, 2) or (n_buckets, 2) operand in SMEM
    (scalar memory: a VMEM block must be (8, 128)-aligned, and a kernel
    reads these as scalars anyway); per-bucket stats are written to a
    whole-array SMEM output the same way;
  * pure VPU elementwise work, no MXU; stochastic rounding compares the
    uniform draw against the fractional part.

Wire format (sub-byte packing): for b-bit codes, pack = 8 // b codes share
one uint8. The wrapper views the padded flat input as (pack, R, C) — pack
contiguous *segments* — and the encode kernel folds the segments'
codes into one (R, C) uint8 payload:

    payload[r, c] = sum_k codes[k, r, c] << (k * b)

Segment packing (rather than packing adjacent lanes) keeps every kernel
access a full aligned (BLOCK_R, C) tile — no cross-lane shuffles — so the
same kernel body serves b in {8, 4, 2} (pack in {1, 2, 4}). The decode
kernel runs a (pack, n_row_tiles) grid, extracting field k = program_id(0)
of each payload tile. The payload IS the wire array: its byte count is
what communicators ship and what the roofline/eventsim consume.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# whole array resident in scalar memory for the entire grid
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _quantize(x, u, lo, scale, levels: int):
    """Shared stochastic-rounding body: fp32 in, fp32 integer codes out."""
    norm = (x.astype(jnp.float32) - lo) / scale
    floor = jnp.floor(norm)
    frac = norm - floor
    q = floor + (u < frac).astype(jnp.float32)
    return jnp.clip(q, 0.0, float(levels))


def _qdq_kernel(params_ref, x_ref, u_ref, o_ref, *, levels: int):
    lo = params_ref[0, 0]
    scale = params_ref[0, 1]
    q = _quantize(x_ref[...], u_ref[...], lo, scale, levels)
    o_ref[...] = (q * scale + lo).astype(o_ref.dtype)


def _encode_packed_kernel(params_ref, x_ref, u_ref, o_ref, *, bits: int):
    """x_ref, u_ref: (pack, BLOCK_R, C) — all segments of one row tile."""
    pack = 8 // bits
    levels = (1 << bits) - 1
    lo = params_ref[0, 0]
    scale = params_ref[0, 1]
    acc = None
    for k in range(pack):
        q = _quantize(x_ref[k], u_ref[k], lo, scale, levels)
        q = q.astype(jnp.int32) << (k * bits)
        acc = q if acc is None else acc | q
    o_ref[...] = acc.astype(jnp.uint8)


def _decode_packed_kernel(params_ref, c_ref, o_ref, *, bits: int):
    k = pl.program_id(0)
    lo = params_ref[0, 0]
    scale = params_ref[0, 1]
    mask = (1 << bits) - 1
    field = (c_ref[...].astype(jnp.int32) >> (k * bits)) & mask
    o_ref[0] = (field.astype(jnp.float32) * scale + lo).astype(o_ref.dtype)


def qdq(x: jnp.ndarray, u: jnp.ndarray, params: jnp.ndarray, *, bits: int,
        block_r: int, interpret: bool) -> jnp.ndarray:
    """x, u: (R, C); params: (1, 2) [lo, scale]. Returns dequantized x."""
    r, c = x.shape
    kernel = functools.partial(_qdq_kernel, levels=(1 << bits) - 1)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, block_r),),
        in_specs=[
            _SMEM,
            pl.BlockSpec((block_r, c), lambda i: (i, 0)),
            pl.BlockSpec((block_r, c), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), x.dtype),
        interpret=interpret,
    )(params, x, u)


def encode_packed(x3: jnp.ndarray, u3: jnp.ndarray, params: jnp.ndarray, *,
                  bits: int, block_r: int, interpret: bool) -> jnp.ndarray:
    """x3, u3: (pack, R, C) segments; returns the (R, C) uint8 payload."""
    pack, r, c = x3.shape
    assert pack == 8 // bits, (pack, bits)
    kernel = functools.partial(_encode_packed_kernel, bits=bits)
    # one (pack, BLOCK_R, C) block per grid step: every segment's tile of
    # the same rows is resident together (pack * BLOCK_R * C fp32 each for
    # x and u — ops.py budgets BLOCK_R accordingly)
    seg_spec = pl.BlockSpec((pack, block_r, c), lambda i: (0, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, block_r),),
        in_specs=[_SMEM, seg_spec, seg_spec],
        out_specs=pl.BlockSpec((block_r, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.uint8),
        interpret=interpret,
    )(params, x3, u3)


def decode_packed(payload: jnp.ndarray, params: jnp.ndarray, *, bits: int,
                  out_dtype, block_r: int, interpret: bool) -> jnp.ndarray:
    """payload: (R, C) uint8 -> (pack, R, C) dequantized segments."""
    r, c = payload.shape
    pack = 8 // bits
    kernel = functools.partial(_decode_packed_kernel, bits=bits)
    return pl.pallas_call(
        kernel,
        grid=(pack, pl.cdiv(r, block_r)),
        in_specs=[
            _SMEM,
            pl.BlockSpec((block_r, c), lambda k, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_r, c), lambda k, i: (k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((pack, r, c), out_dtype),
        interpret=interpret,
    )(params, payload)


# ---------------------------------------------------------------------------
# Bucketed (fused flat-buffer) kernels. The whole gradient pytree arrives as
# ONE (n_buckets, pack, Rb, C) buffer; each bucket has its own (lo, scale)
# row in an (n_buckets, 2) params array. The grid gains a leading bucket
# dimension whose index selects the params row, so every block still reads a
# full aligned tile and the kernel bodies stay pure-VPU elementwise — the
# same shapes-in/shapes-out contract as the per-leaf kernels, just with
# per-bucket scales. Bit-identical to ref.*_bucketed for the same uniforms.
# ---------------------------------------------------------------------------


def _qdq_bucketed_kernel(params_ref, x_ref, u_ref, o_ref, *, levels: int):
    """x_ref, u_ref, o_ref: (1, pack, BLOCK_R, C); params_ref is the FULL
    (n_buckets, 2) params array, resident in SMEM for the whole grid (no
    per-step refetch of the (lo, scale) row; the kernel picks its bucket's
    row by program id)."""
    bi = pl.program_id(0)
    lo = params_ref[bi, 0]
    scale = params_ref[bi, 1]
    q = _quantize(x_ref[...], u_ref[...], lo, scale, levels)
    o_ref[...] = (q * scale + lo).astype(o_ref.dtype)


def _encode_packed_bucketed_kernel(params_ref, x_ref, u_ref, o_ref, *,
                                   bits: int):
    """x_ref, u_ref: (1, pack, BLOCK_R, C) — one bucket's row tile, all
    segments; o_ref: (1, BLOCK_R, C) packed payload tile; params_ref: the
    full SMEM-resident (n_buckets, 2) array (see _qdq_bucketed_kernel)."""
    pack = 8 // bits
    levels = (1 << bits) - 1
    bi = pl.program_id(0)
    lo = params_ref[bi, 0]
    scale = params_ref[bi, 1]
    acc = None
    for k in range(pack):
        q = _quantize(x_ref[0, k], u_ref[0, k], lo, scale, levels)
        q = q.astype(jnp.int32) << (k * bits)
        acc = q if acc is None else acc | q
    o_ref[0] = acc.astype(jnp.uint8)


def _decode_packed_bucketed_kernel(params_ref, c_ref, o_ref, *, bits: int):
    k = pl.program_id(0)
    bi = pl.program_id(1)
    lo = params_ref[bi, 0]
    scale = params_ref[bi, 1]
    mask = (1 << bits) - 1
    field = (c_ref[0].astype(jnp.int32) >> (k * bits)) & mask
    o_ref[0, 0] = (field.astype(jnp.float32) * scale + lo).astype(o_ref.dtype)


def qdq_bucketed(x4: jnp.ndarray, u4: jnp.ndarray, params: jnp.ndarray, *,
                 bits: int, block_r: int, interpret: bool) -> jnp.ndarray:
    """x4, u4: (B, pack, Rb, C); params: (B, 2). Returns dequantized x4."""
    b, pack, r, c = x4.shape
    kernel = functools.partial(_qdq_bucketed_kernel, levels=(1 << bits) - 1)
    seg = pl.BlockSpec((1, pack, block_r, c), lambda bi, i: (bi, 0, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, pl.cdiv(r, block_r)),
        in_specs=[_SMEM, seg, seg],
        out_specs=seg,
        out_shape=jax.ShapeDtypeStruct((b, pack, r, c), x4.dtype),
        interpret=interpret,
    )(params, x4, u4)


def encode_packed_bucketed(x4: jnp.ndarray, u4: jnp.ndarray,
                           params: jnp.ndarray, *, bits: int, block_r: int,
                           interpret: bool) -> jnp.ndarray:
    """x4, u4: (B, pack, Rb, C) bucket segments; returns (B, Rb, C) uint8."""
    b, pack, r, c = x4.shape
    assert pack == 8 // bits, (x4.shape, bits)
    kernel = functools.partial(_encode_packed_bucketed_kernel, bits=bits)
    seg = pl.BlockSpec((1, pack, block_r, c), lambda bi, i: (bi, 0, i, 0))
    return pl.pallas_call(
        kernel,
        grid=(b, pl.cdiv(r, block_r)),
        in_specs=[_SMEM, seg, seg],
        out_specs=pl.BlockSpec((1, block_r, c), lambda bi, i: (bi, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r, c), jnp.uint8),
        interpret=interpret,
    )(params, x4, u4)


def _minmax_bucketed_kernel(x_ref, o_ref, *, n_rows: int, block_r: int):
    """x_ref: (1, BLOCK_R, C) one bucket's row tile; o_ref: the whole
    (B, 2) SMEM output, whose row bi holds the bucket's [lo, hi],
    accumulated across the (sequential) row-tile grid dimension — a
    single-read fused min+max reduction. Rows past
    n_rows (grid padding of the last tile) are masked out of the
    reduction: padded values must never touch the bucket's range."""
    bi = pl.program_id(0)
    i = pl.program_id(1)
    x = x_ref[0]
    row = i * block_r + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    valid = row < n_rows
    tile_lo = jnp.min(jnp.where(valid, x, jnp.inf))
    tile_hi = jnp.max(jnp.where(valid, x, -jnp.inf))

    @pl.when(i == 0)
    def _init():
        o_ref[bi, 0] = tile_lo
        o_ref[bi, 1] = tile_hi

    @pl.when(i > 0)
    def _acc():
        o_ref[bi, 0] = jnp.minimum(o_ref[bi, 0], tile_lo)
        o_ref[bi, 1] = jnp.maximum(o_ref[bi, 1], tile_hi)


def minmax_bucketed(x3: jnp.ndarray, *, block_r: int,
                    interpret: bool) -> jnp.ndarray:
    """x3: (B, R, C) fp32 bucket view -> (B, 2) per-bucket [lo, hi].

    One read of the buffer (min and max in the same pass), vs the two
    separate reduction passes of jnp.min + jnp.max. min/max accumulate
    exactly, so the result is bit-identical to the jnp reference.
    """
    b, r, c = x3.shape
    kernel = functools.partial(_minmax_bucketed_kernel, n_rows=r,
                               block_r=block_r)
    return pl.pallas_call(
        kernel,
        grid=(b, pl.cdiv(r, block_r)),
        in_specs=[pl.BlockSpec((1, block_r, c), lambda bi, i: (bi, i, 0))],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((b, 2), jnp.float32),
        interpret=interpret,
    )(x3)


# ---------------------------------------------------------------------------
# Fused ring hop: decode + add + re-encode in ONE kernel. A reduce-scatter
# hop's work on a partition used to be three dispatches with two full fp32
# temporaries between them (the decoded message, then the sum); here the
# grid runs TWO phases over each bucket — steps [0, n_tiles) decode the
# payload tile, add the local tile, and min/max-accumulate the new bucket
# range into a (2,) SMEM scratch; steps [n_tiles, 2*n_tiles) recompute
# the same decode+add (recompute beats materializing: the fp32 sum never
# exists outside VMEM) and quantize/bit-pack it with the scratch-held
# (lo, scale). The params output (the whole (B, 2) array in SMEM) gets
# row bi at the bucket's last stats step; the payload output's index map
# parks all stats steps on block 0, so every output block's revisits stay
# consecutive (TPU flush rule) and its final visit is the encode step that
# writes it. Bit-identical to the sequential
# decode -> add -> minmax -> encode chain: same decoded values, same adds,
# exact min/max, same _quantize math, same (externally drawn) uniforms.
# ---------------------------------------------------------------------------


def _decode_add_encode_bucketed_kernel(params_ref, pay_ref, x_ref, u_ref,
                                       out_ref, pout_ref, mm_scr, *,
                                       bits: int, n_tiles: int, n_rows: int,
                                       block_r: int):
    """params_ref: full SMEM-resident (B, 2) [lo, scale] of the INCOMING
    message; pay_ref: (1, BLOCK_R, C) incoming payload tile; x_ref, u_ref:
    (1, pack, BLOCK_R, C) local-addend / uniform tiles; out_ref: (1,
    BLOCK_R, C) re-encoded payload tile; pout_ref: whole (B, 2) SMEM output, row bi
    this bucket's new params; mm_scr: (2,) SMEM carry — [lo, hi] during
    stats, [lo, scale] after."""
    bi = pl.program_id(0)
    i = pl.program_id(1)
    pack = 8 // bits
    levels = (1 << bits) - 1
    lo_in = params_ref[bi, 0]
    scale_in = params_ref[bi, 1]

    # decode + add — needed by both phases (recompute, never materialized)
    codes = pay_ref[0].astype(jnp.int32)
    summed = [
        ((codes >> (k * bits)) & levels).astype(jnp.float32) * scale_in
        + lo_in + x_ref[0, k].astype(jnp.float32)
        for k in range(pack)
    ]

    @pl.when(i == 0)
    def _init():
        mm_scr[0] = jnp.float32(jnp.inf)
        mm_scr[1] = jnp.float32(-jnp.inf)

    @pl.when(i < n_tiles)
    def _stats():
        # rows past n_rows are grid padding of the last tile — masked out
        row = (jax.lax.rem(i, n_tiles) * block_r
               + jax.lax.broadcasted_iota(jnp.int32, summed[0].shape, 0))
        valid = row < n_rows
        lo_t = jnp.float32(jnp.inf)
        hi_t = jnp.float32(-jnp.inf)
        for s in summed:
            lo_t = jnp.minimum(lo_t, jnp.min(jnp.where(valid, s, jnp.inf)))
            hi_t = jnp.maximum(hi_t, jnp.max(jnp.where(valid, s, -jnp.inf)))
        mm_scr[0] = jnp.minimum(mm_scr[0], lo_t)
        mm_scr[1] = jnp.maximum(mm_scr[1], hi_t)

    @pl.when(i == n_tiles - 1)
    def _finalize_params():
        lo = mm_scr[0]
        hi = mm_scr[1]
        scale = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
        pout_ref[bi, 0] = lo
        pout_ref[bi, 1] = scale
        mm_scr[1] = scale             # phase 2 reads [lo, scale]

    @pl.when(i >= n_tiles)
    def _encode():
        lo = mm_scr[0]
        scale = mm_scr[1]
        acc = None
        for k in range(pack):
            q = _quantize(summed[k], u_ref[0, k], lo, scale, levels)
            q = q.astype(jnp.int32) << (k * bits)
            acc = q if acc is None else acc | q
        out_ref[0] = acc.astype(jnp.uint8)


def decode_add_encode_bucketed(payload: jnp.ndarray, params: jnp.ndarray,
                               x4: jnp.ndarray, u4: jnp.ndarray, *,
                               bits: int, block_r: int, interpret: bool):
    """Fused per-bucket ring hop. payload: (B, Rb, C) uint8 incoming;
    params: (B, 2) its [lo, scale] rows; x4: (B, pack, Rb, C) fp32 local
    addend segments; u4: matching uniforms for the re-encode. Returns
    (payload_out (B, Rb, C) uint8, params_out (B, 2) fp32)."""
    b, r, c = payload.shape
    _, pack, _, _ = x4.shape
    assert pack == 8 // bits, (x4.shape, bits)
    n_tiles = pl.cdiv(r, block_r)
    kernel = functools.partial(
        _decode_add_encode_bucketed_kernel, bits=bits, n_tiles=n_tiles,
        n_rows=r, block_r=block_r)
    seg = pl.BlockSpec((1, pack, block_r, c),
                       lambda bi, i, nt=n_tiles:
                       (bi, 0, jax.lax.rem(i, nt), 0))
    return pl.pallas_call(
        kernel,
        grid=(b, 2 * n_tiles),
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, block_r, c),
                         lambda bi, i, nt=n_tiles:
                         (bi, jax.lax.rem(i, nt), 0)),
            seg,
            seg,
        ],
        out_specs=[
            # stats steps park on block 0 so revisits stay consecutive;
            # its last visit (the first encode step) writes it
            pl.BlockSpec((1, block_r, c),
                         lambda bi, i, nt=n_tiles:
                         (bi, jnp.where(i < nt, 0, i - nt), 0)),
            _SMEM,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, r, c), jnp.uint8),
            jax.ShapeDtypeStruct((b, 2), jnp.float32),
        ],
        scratch_shapes=[pltpu.SMEM((2,), jnp.float32)],
        interpret=interpret,
    )(params, payload, x4, u4)


def decode_packed_bucketed(payload: jnp.ndarray, params: jnp.ndarray, *,
                           bits: int, out_dtype, block_r: int,
                           interpret: bool) -> jnp.ndarray:
    """payload: (B, Rb, C) uint8 -> (B, pack, Rb, C) dequantized segments."""
    b, r, c = payload.shape
    pack = 8 // bits
    kernel = functools.partial(_decode_packed_bucketed_kernel, bits=bits)
    return pl.pallas_call(
        kernel,
        grid=(pack, b, pl.cdiv(r, block_r)),
        in_specs=[
            _SMEM,
            pl.BlockSpec((1, block_r, c), lambda k, bi, i: (bi, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_r, c),
                               lambda k, bi, i: (bi, k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, pack, r, c), out_dtype),
        interpret=interpret,
    )(params, payload)
