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
    uniform draw against the fractional part (the flat qdq kernel draws
    it in VMEM, the others read it from HBM).

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
from jax import lax
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


def _encode_packed_bucketed_kernel(params_ref, x_ref, u_ref, o_ref, *,
                                   bits: int):
    """x_ref, u_ref: (1, pack, BLOCK_R, C) — one bucket's row tile, all
    segments; o_ref: (1, BLOCK_R, C) packed payload tile; params_ref: the
    FULL (n_buckets, 2) params array, resident in SMEM for the whole grid
    (no per-step refetch of the (lo, scale) row; the kernel picks its
    bucket's row by program id)."""
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


# ---------------------------------------------------------------------------
# Flat-buffer quantize-dequantize in two passes over the caller's unpadded
# (N,) buffer, viewed as (R, ROW) rows — a bitcast of the flat array when
# N % ROW == 0. A bucket is a whole number of (8, ROW) tiles (ops.flat_
# geometry aligns its cap to 8 * ROW elements) and each grid block lies in
# one bucket (ops picks BLOCK_R to divide the bucket's rows), so a block
# reads its bucket's row of the SMEM-resident (n_buckets, 2) tables as
# scalars. Rows past the end of the view (the ragged last block) and
# elements past N are masked out of the stats; the coding pass writes them
# nowhere that survives (Pallas drops a partial block's out-of-bounds rows).
#
# The coding pass draws its own uniforms: under jax_threefry_partitionable,
# jax.random.uniform(k, shape) gives the element at C-order index i from
# threefry2x32(k, (0, i)), whatever the shape, so bucket b's element c
# (its C-order index in the bucket's (pack, rows, 512) draw) gets
# threefry_uniform(fold_in(key, b), c) — the same bits the jnp backend and
# encode_flat draw in HBM, computed in VMEM instead.
# ---------------------------------------------------------------------------

ROW = 128
_THREEFRY_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))
_THREEFRY_PARITY = 0x1BD11BDA


def threefry_uniform(k0, k1, ctr):
    """``jax.random.uniform``'s float32 draw for counter ``ctr`` under the
    raw threefry key (k0, k1), bit for bit.

    All words are int32 bit patterns: adds wrap and right shifts are
    logical, so every op matches its uint32 counterpart. The counter's
    high word is 0 (a bucket holds under 2**32 elements). The two output
    words are XORed into 32 random bits; the top 23 become the mantissa
    of a float in [1, 2), minus 1."""
    ks = (k0, k1, k0 ^ k1 ^ _THREEFRY_PARITY)
    x0 = k0
    x1 = ctr + k1
    for i in range(5):
        for r in _THREEFRY_ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = (x1 << r) | lax.shift_right_logical(x1, 32 - r)
            x1 = x0 ^ x1
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + (ks[(i + 2) % 3] + (i + 1))
    bits = lax.shift_right_logical(x0 ^ x1, 9) | 0x3F800000
    return lax.bitcast_convert_type(bits, jnp.float32) - 1.0


def _block_bucket(block_r: int, rows_b: int, nb: int):
    """The bucket of this grid step's block, and the block's first row."""
    r0 = pl.program_id(0) * block_r
    return jnp.minimum(r0 // rows_b, nb - 1), r0


def _minmax_flat_kernel(x_ref, o_ref, *, total: int, rows_b: int, nb: int,
                        block_r: int):
    """x_ref: (BLOCK_R, ROW) block; o_ref: the whole (nb, 2) SMEM output,
    row b the bucket's [lo, hi], accumulated over the (sequential) grid."""
    b, r0 = _block_bucket(block_r, rows_b, nb)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        def row(j, carry):
            o_ref[j, 0] = jnp.float32(jnp.inf)
            o_ref[j, 1] = jnp.float32(-jnp.inf)
            return carry
        lax.fori_loop(0, nb, row, 0)

    x = x_ref[...]
    idx = (lax.broadcasted_iota(jnp.int32, x.shape, 0) * ROW
           + lax.broadcasted_iota(jnp.int32, x.shape, 1))
    valid = idx < total - r0 * ROW
    o_ref[b, 0] = jnp.minimum(o_ref[b, 0],
                              jnp.min(jnp.where(valid, x, jnp.inf)))
    o_ref[b, 1] = jnp.maximum(o_ref[b, 1],
                              jnp.max(jnp.where(valid, x, -jnp.inf)))


def minmax_flat(x2: jnp.ndarray, *, total: int, rows_b: int, nb: int,
                block_r: int, interpret: bool) -> jnp.ndarray:
    """x2: (R, ROW) view of a flat buffer of ``total`` elements ->
    (nb, 2) per-bucket [lo, hi] in one read. min/max are exact, so the
    result equals any other reduction order's bit for bit."""
    r, c = x2.shape
    assert c == ROW and (nb == 1 or rows_b % block_r == 0), (
        x2.shape, rows_b, block_r)
    kernel = functools.partial(_minmax_flat_kernel, total=total,
                               rows_b=rows_b, nb=nb, block_r=block_r)
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, block_r),),
        in_specs=[pl.BlockSpec((block_r, c), lambda i: (i, 0))],
        out_specs=_SMEM,
        out_shape=jax.ShapeDtypeStruct((nb, 2), jnp.float32),
        interpret=interpret,
    )(x2)


def _qdq_flat_kernel(keys_ref, params_ref, x_ref, o_ref, *, levels: int,
                     rows_b: int, nb: int, block_r: int, slab: int):
    """keys_ref: (nb, 2) int32 bucket keys and params_ref: (nb, 2) [lo,
    scale], both whole in SMEM; x_ref, o_ref: (BLOCK_R, ROW) blocks,
    coded ``slab`` rows at a time so the threefry rounds stay in vregs."""
    b, r0 = _block_bucket(block_r, rows_b, nb)
    k0, k1 = keys_ref[b, 0], keys_ref[b, 1]
    lo, scale = params_ref[b, 0], params_ref[b, 1]
    # counter of the block's first element in its bucket's draw
    first = (r0 - b * rows_b) * ROW
    offset = (lax.broadcasted_iota(jnp.int32, (slab, ROW), 0) * ROW
              + lax.broadcasted_iota(jnp.int32, (slab, ROW), 1))

    def code(s, carry):
        rows = pl.ds(pl.multiple_of(s * slab, slab), slab)
        u = threefry_uniform(k0, k1, offset + (first + s * slab * ROW))
        q = _quantize(x_ref[rows, :], u, lo, scale, levels)
        o_ref[rows, :] = (q * scale + lo).astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, block_r // slab, code, 0)


def qdq_flat(x2: jnp.ndarray, keys: jnp.ndarray, params: jnp.ndarray, *,
             bits: int, rows_b: int, block_r: int, slab: int,
             interpret: bool) -> jnp.ndarray:
    """x2: (R, ROW) view of a flat buffer; keys: (nb, 2) int32 raw
    threefry keys of the buckets; params: (nb, 2) [lo, scale]. Returns
    the dequantized (R, ROW) view."""
    r, c = x2.shape
    nb = params.shape[0]
    assert c == ROW and block_r % slab == 0 and (
        nb == 1 or rows_b % block_r == 0), (x2.shape, rows_b, block_r, slab)
    kernel = functools.partial(_qdq_flat_kernel, levels=(1 << bits) - 1,
                               rows_b=rows_b, nb=nb, block_r=block_r,
                               slab=slab)
    block = pl.BlockSpec((block_r, c), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, block_r),),
        in_specs=[_SMEM, _SMEM, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((r, c), x2.dtype),
        interpret=interpret,
    )(keys, params, x2)


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
