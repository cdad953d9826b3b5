"""jit'd wrappers for the quantization kernels, with backend dispatch.

Handles arbitrary shapes, computes (lo, scale), picks BLOCK_R per kernel
from the actual resident operand dtypes, and dispatches between the two
backends:

  backend='pallas'  the TPU kernels (interpret=True off-TPU)
  backend='jnp'     the pure-jnp reference (ref.py)
  backend='auto'    pallas on TPU, jnp elsewhere

The per-leaf and packed-wire paths pad + reshape to C=512 lanes and draw
their uniforms in HBM with `jax.random.uniform`; the flat-buffer qdq on
the Pallas backend reads the caller's unpadded buffer and draws the same
uniforms inside its kernel (kernel.threefry_uniform). Both backends
consume the *same* (lo, scale) and the same per-element uniforms —
`jax.random.uniform` fills shapes in flat C-order, so the (pack, R, C)
segment view of encode and the (R*pack, C) view of qdq read identical
per-element uniforms. Consequence (asserted in tests/test_codec.py and
tests/test_flat_codec.py):

    decode(encode(x, key)) == quantize_dequantize(x, key)   bit-for-bit
    pallas(interpret) == jnp                                bit-for-bit

Wire layout: the padded flat array is split into pack = 8 // bits
contiguous segments of R rows x 512 lanes; element i of the flat input
lives at segment i // (R*512), bit-field (i // (R*512)) * bits of
payload byte i % (R*512). Padding: inputs are zero-padded to a multiple
of pack * 512 elements; payload bytes = ceil(n / (pack*512)) * 512.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from repro.obs import flight as obs_flight
from repro.kernels.quant import kernel, ref

LANES = 512
# Three quarters of the 16 MiB scoped-VMEM limit Mosaic applies by default
# on v5e; the rest is headroom for the compiler's own scratch.
VMEM_BUDGET = 12 * 1024 * 1024

# Fused flat-buffer tier: elements per quantization bucket (4Mi elements =
# 16 MiB fp32 per bucket -> a 100M-param gradient is ~25-31 (lo, scale)
# rows instead of one per pytree leaf). Canonical definition;
# repro.core.compression re-exports it.
DEFAULT_BUCKET_ELEMS = 1 << 22


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _use_pallas(backend: str) -> bool:
    if backend == "auto":
        return jax.default_backend() == "tpu"
    if backend not in ("pallas", "jnp"):
        raise ValueError(f"unknown backend '{backend}'")
    return backend == "pallas"


def _block_r(c: int, io_bytes: int, f32_elems: int) -> int:
    """Rows per grid step such that what one step keeps in VMEM fits
    VMEM_BUDGET.

    Per element-column of one block row, a step holds every blocked
    in/out tile twice (Pallas double-buffers them: ``2 * io_bytes``) plus
    the body's temporaries: about two fp32 values per fp32 element of the
    tile (``f32_elems`` of them per row element) and one int32 packing
    accumulator. ``io_bytes`` sums the tiles' bytes per row element: qdq
    has x, u and out fp32 (12); a packed encode has pack fp32 x-segments,
    pack fp32 u-segments and one uint8 out (8 * pack + 1); decode has one
    uint8 in and one fp32 out (5); the flat qdq has x and out fp32 (8)
    and keeps its temporaries per slab, not per tile (0). Checked against
    the v5e compiler at rq8/rq4/rq2 by tests/test_tpu_compile.py.
    """
    per_elem = 2 * io_bytes + 8 * f32_elems + 4
    rows = VMEM_BUDGET // (per_elem * c)
    rows = max(8, min(1024 * LANES // c, rows))   # <= 512Ki elements
    return int(rows) & ~7 or 8   # multiple of 8 sublanes


def _to_2d(x: jnp.ndarray, multiple: int = 1) -> jnp.ndarray:
    """Flatten + zero-pad to (R, LANES) with R a multiple of `multiple`."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % (LANES * multiple)
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES)


def _params_for(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    lo, scale = ref.quant_params(x, bits)
    return jnp.stack([lo, scale]).reshape(1, 2)


@partial(jax.jit, static_argnames=("bits", "backend"))
@obs_flight.kernel_annotation("quant.qdq")
def quantize_dequantize(x: jnp.ndarray, key: jax.Array, *, bits: int = 8,
                        backend: str = "auto") -> jnp.ndarray:
    """Fused Q(x) with stochastic rounding; same statistics as
    repro.core.compression.randomized_quantize."""
    params = _params_for(x, bits)
    # pad to the same multiple as the packed wire layout so qdq and
    # decode(encode(.)) consume identical uniform draws (threefry bit
    # generation is not prefix-stable across different totals)
    x2d = _to_2d(x, multiple=8 // bits)
    u = jax.random.uniform(key, x2d.shape, jnp.float32)
    if _use_pallas(backend):
        out = kernel.qdq(x2d, u, params, bits=bits,
                         block_r=_block_r(x2d.shape[1], 12, 1),
                         interpret=_interpret())
    else:
        # direct qdq: skips the encode -> uint8 -> decode round trip (a
        # lossless detour — bit-identical, see ref.qdq) so XLA fuses the
        # whole rounding chain into one elementwise pass
        out = ref.qdq(x2d, u, params[0, 0], params[0, 1], bits=bits)
    return out.reshape(-1)[: x.size].reshape(x.shape).astype(x.dtype)


@partial(jax.jit, static_argnames=("bits", "backend"))
@obs_flight.kernel_annotation("quant.encode")
def encode(x: jnp.ndarray, key: jax.Array, *, bits: int = 8,
           backend: str = "auto"):
    """Returns (payload uint8 (R, 512), params (1, 2)).

    The payload is the packed wire array: payload.size bytes carry
    8 // bits codes per byte. Wire bytes = payload.nbytes + params.nbytes.
    """
    pack = 8 // bits
    params = _params_for(x, bits)
    x3 = _to_2d(x, multiple=pack).reshape(pack, -1, LANES)
    u = jax.random.uniform(key, x3.shape, jnp.float32)
    if _use_pallas(backend):
        payload = kernel.encode_packed(
            x3, u, params, bits=bits,
            block_r=_block_r(x3.shape[2], 8 * pack + 1, pack),
            interpret=_interpret())
    else:
        payload = ref.encode_packed(x3, u, params[0, 0], params[0, 1],
                                    bits=bits)
    return payload, params


@partial(jax.jit, static_argnames=("bits", "shape", "dtype", "backend"))
@obs_flight.kernel_annotation("quant.decode")
def decode(payload: jnp.ndarray, params: jnp.ndarray, *, shape: tuple,
           bits: int = 8, dtype=jnp.float32, backend: str = "auto"):
    """Unpack + dequantize a wire payload back to `shape`."""
    if _use_pallas(backend):
        out3 = kernel.decode_packed(
            payload, params, bits=bits, out_dtype=jnp.float32,
            block_r=_block_r(payload.shape[1], 1 + 4, 1),
            interpret=_interpret())
    else:
        out3 = ref.decode_packed(payload, params[0, 0], params[0, 1],
                                 bits=bits)
    size = 1
    for d in shape:
        size *= d
    return out3.reshape(-1)[:size].reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Fused flat-buffer tier: the whole gradient pytree as ONE buffer, segmented
# into size-capped buckets with an (n_buckets, 2) params array. Wire layout:
# bucket b owns the contiguous element range [b*cap, (b+1)*cap) of the flat
# buffer and is segment-packed *within itself* (the per-leaf layout, applied
# per bucket). Full buckets contribute Rb = cap // (pack*512) payload rows
# each; the (possibly short) LAST bucket is padded only to the pack*512
# granule and gets its own, smaller segment view of Rt = ceil(t / (pack*512))
# rows — trimming rows of a cap-sized view would drop real elements, because
# segment packing interleaves the whole bucket range into every row. So the
# whole-tree message pays at most ONE pad granule (the tail's) plus one
# 8-byte params row per bucket — vs one granule + one row per leaf on the
# per-leaf paths. Kernel cost is O(1) in the leaf count: the wire kernels
# make one bucketed call for the full buckets + one per-leaf-style call for
# the tail; the flat qdq makes one stats call and one coding call over the
# whole unpadded buffer.
# ---------------------------------------------------------------------------


def _align_up(x: int, m: int) -> int:
    return -(-x // m) * m


def edge_pad(flat: jnp.ndarray, padded_len: int) -> jnp.ndarray:
    """Zero-copy-pipeline edge pad: write `flat` and a broadcast of its
    last element into one preallocated buffer via dynamic_update_slice
    (``jnp.pad(mode='edge')`` lowers through concatenate — the copy tax
    this tier exists to avoid). Repeating the last REAL element keeps the
    pad out of every bucket's (lo, hi)."""
    n = flat.shape[0]
    if padded_len == n:
        return flat
    out = jnp.zeros((padded_len,), flat.dtype)
    out = lax.dynamic_update_slice(out, flat, (0,))
    tail = jnp.broadcast_to(flat[-1], (padded_len - n,))
    return lax.dynamic_update_slice(out, tail, (n,))


def _stack2(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(B,), (B,) -> (B, 2) without a concatenate/stack op (single-buffer
    writes, same contract as the payload assembly below)."""
    out = jnp.zeros((a.shape[0], 2), jnp.float32)
    out = lax.dynamic_update_slice(out, a.astype(jnp.float32)[:, None],
                                   (0, 0))
    return lax.dynamic_update_slice(out, b.astype(jnp.float32)[:, None],
                                    (0, 1))


def _rows(flat: jnp.ndarray) -> jnp.ndarray:
    """The flat buffer as (R, kernel.ROW) rows: a bitcast when its length
    is a multiple of ROW, else after a zero pad to the next row (the flat
    kernels mask or drop the pad)."""
    pad = -flat.shape[0] % kernel.ROW
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, kernel.ROW)


def _flat_block_r(block_r: int, rows_b: int, nb: int) -> int:
    """``block_r`` cut to a multiple of 8 that divides the bucket's rows
    when there are several buckets, so every block of the flat kernels
    lies in one bucket (``flat_geometry`` makes rows_b a multiple of 8)."""
    if nb > 1:
        block_r = min(block_r, rows_b)
        while rows_b % block_r:
            block_r -= 8
    return block_r


def bucket_params(flat: jnp.ndarray, *, bits: int, bucket_elems: int,
                  backend: str) -> jnp.ndarray:
    """Per-bucket (n_buckets, 2) [lo, scale] rows of a flat buffer in ONE
    read: min and max come out of the same reduction pass (the Pallas
    ``minmax_flat`` kernel over the unpadded buffer, masked past its end,
    on the pallas backend; a variadic ``lax.reduce`` over the edge-padded
    (n_buckets, cap) view on the jnp reference — the edge pad repeats the
    last real element, so both see the same (lo, hi)). The stats pass
    cannot fuse further into the coding kernel itself — stochastic
    rounding needs the bucket-global (lo, scale) before any element can
    be coded — so the flat pipeline's floor is two reads: one fused stats
    pass + one coding pass."""
    total = flat.shape[0]
    _, cap, nb, _, _ = flat_geometry(total, bits=bits,
                                     bucket_elems=bucket_elems)
    levels = (1 << bits) - 1
    if _use_pallas(backend):
        rows_b = cap // kernel.ROW
        mm = kernel.minmax_flat(
            _rows(flat), total=total, rows_b=rows_b, nb=nb,
            block_r=_flat_block_r(_block_r(kernel.ROW, 4, 1), rows_b, nb),
            interpret=_interpret())
        lo, hi = mm[:, 0], mm[:, 1]
    else:
        lo, hi = ref.minmax_bucketed(edge_pad(flat, nb * cap).reshape(nb,
                                                                      cap))
    scale = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
    return _stack2(lo, scale)


def partition_geometry(total: int, n_parts: int, *, bits: int,
                       bucket_elems: int = DEFAULT_BUCKET_ELEMS):
    """Equal, granule-aligned N-way partition view of a flat buffer (the
    ring AllReduce's reduce-scatter/all-gather unit).

    Returns (part_elems, nb_p, rows_p): each of the n_parts partitions
    owns part_elems contiguous elements of the (edge-padded to
    n_parts * part_elems) flat buffer — granule-aligned, so every
    partition segment-packs independently — and has its own bucket rows:
    nb_p (lo, scale) params rows and rows_p payload rows. Per-partition
    wire bytes = rows_p * LANES + nb_p * 8; a full partitioned exchange
    ships 2(N-1) of these per worker = 2*M*(N-1)/N + at most one pad
    granule per partition.
    """
    if n_parts <= 0:
        raise ValueError(f"need n_parts >= 1, got {n_parts}")
    pack = 8 // bits
    granule = pack * LANES
    part_elems = _align_up(max(1, -(-total // n_parts)), granule)
    _, _, nb_p, _, rows_p = flat_geometry(part_elems, bits=bits,
                                          bucket_elems=bucket_elems)
    return part_elems, nb_p, rows_p


def flat_geometry(total: int, *, bits: int,
                  bucket_elems: int = DEFAULT_BUCKET_ELEMS):
    """Static bucket geometry for a flat buffer of `total` elements.

    Returns (pack, cap, n_buckets, rows_per_bucket, rows_kept):
      cap             elements per full bucket (`bucket_elems`, shrunk
                      for small buffers, rounded up to a whole granule
                      and a whole number of the flat kernels' (8, 128)
                      tiles);
      rows_per_bucket payload rows each full bucket contributes;
      rows_kept       total payload rows on the wire — Rb per full bucket
                      plus the tail bucket's granule-aligned Rt.
    """
    if total <= 0:
        raise ValueError(f"empty flat buffer (total={total})")
    pack = 8 // bits
    granule = pack * LANES                      # elements per payload row
    cap = _align_up(min(bucket_elems, total), max(granule, 8 * kernel.ROW))
    n_buckets = -(-total // cap)
    rows_b = cap // granule
    tail = total - (n_buckets - 1) * cap        # in (0, cap]
    rows_kept = (n_buckets - 1) * rows_b + -(-tail // granule)
    return pack, cap, n_buckets, rows_b, rows_kept


def bucket_key(key, b):
    """Bucket b's uniform-draw key: fold_in(key, b). The SINGLE source of
    per-bucket randomness for every fused path — encode_flat and the jnp
    qdq_flat draw under it in HBM (one vmapped draw, bit-identical to
    per-key draws because threefry is counter-based), the cache-blocked
    from-tree encode draws under it per bucket, and the Pallas qdq_flat
    hands its raw words to the coding kernel, which draws the same bits
    in VMEM (``bucket_keys``, ``kernel.threefry_uniform``)."""
    return jax.random.fold_in(key, b)


def bucket_keys(key, nb: int) -> jnp.ndarray:
    """(nb, 2) int32 raw threefry words of ``bucket_key(key, b)`` for every
    bucket: what the Pallas coding kernel draws under. Its formula is
    jax.random.uniform's under ``jax_threefry_partitionable``, so anything
    else is refused rather than drawn differently."""
    keys = jax.vmap(partial(bucket_key, key))(jnp.arange(nb))
    if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
        keys = jax.random.key_data(keys)
    if not jax.config.jax_threefry_partitionable or keys.shape != (nb, 2):
        raise ValueError(
            "the Pallas flat codec draws jax.random.uniform's bits for "
            "threefry2x32 keys under jax_threefry_partitionable=True; got "
            f"key words {keys.shape}, partitionable="
            f"{jax.config.jax_threefry_partitionable}")
    return lax.bitcast_convert_type(keys, jnp.int32)


def _bucket_views(flat: jnp.ndarray, key, *, bits: int, bucket_elems: int,
                  backend: str):
    """Split a flat buffer into head/tail segment views + per-bucket params.

    The buffer is edge-padded ONCE (single-buffer writes, no concatenate)
    to n_buckets * cap; the head's (B-1, pack, Rb, C) segments and the
    tail's (pack, Rt, C) segments are slices/reshapes of that one padded
    buffer. Per-bucket [lo, scale] come from ``bucket_params`` (min+max
    fused into one reduction read). Edge padding repeats the last REAL
    element, so the pad never perturbs the tail bucket's (lo, hi).
    Uniforms are drawn PER BUCKET under ``bucket_key(key, b)`` (head
    buckets via one vmapped draw), so encode_flat, the jnp qdq_flat, the
    cache-blocked from-tree encode and the Pallas qdq_flat's in-kernel
    draw all consume identical per-element randomness (bit-identical
    results)."""
    pack, cap, nb, rows_b, _ = flat_geometry(flat.size, bits=bits,
                                             bucket_elems=bucket_elems)
    granule = pack * LANES
    flat = flat.reshape(-1).astype(jnp.float32)
    total = flat.shape[0]
    head_elems = (nb - 1) * cap
    t = total - head_elems
    rt = -(-t // granule)
    padded = edge_pad(flat, nb * cap)
    params = bucket_params(flat, bits=bits, bucket_elems=bucket_elems,
                           backend=backend)
    x4 = u4 = None
    if nb > 1:
        x4 = padded[:head_elems].reshape(nb - 1, pack, rows_b, LANES)
        if key is not None:
            hkeys = jax.vmap(lambda b: bucket_key(key, b))(
                jnp.arange(nb - 1))
            u4 = jax.vmap(
                lambda k: jax.random.uniform(k, (pack, rows_b, LANES),
                                             jnp.float32))(hkeys)
    x3 = padded[head_elems:head_elems + rt * granule].reshape(pack, rt,
                                                              LANES)
    u3 = (None if key is None else
          jax.random.uniform(bucket_key(key, nb - 1), x3.shape,
                             jnp.float32))
    return x4, u4, x3, u3, params, (pack, nb, rows_b, rt, t)


def _write_head_tail(head, tail, out_shape, dtype):
    """Assemble the fused result by writing head + tail into ONE
    preallocated output (dynamic_update_slice) instead of concatenating —
    the copy that made the PR-2 flat path a measured compute regression.
    head is None in the single-bucket regime (the tail IS the result)."""
    if head is None:
        return tail.astype(dtype)
    out = jnp.zeros(out_shape, dtype)
    out = lax.dynamic_update_slice(out, head.astype(dtype),
                                   (0,) * len(out_shape))
    off = (head.shape[0],) + (0,) * (len(out_shape) - 1)
    return lax.dynamic_update_slice(out, tail.astype(dtype), off)


# Rows the coding kernel codes at a time: each op of the threefry rounds
# then runs SLAB_ROWS // 8 independent vregs, enough to keep the VPU busy.
# Swept on a TPU v5e at the repro-100m gradient: 8 / 16 / 32 / 64 / 128 rows
# took 14.6 / 8.9 / 5.6 / 4.3 / 4.3 ms per qdq_flat call.
SLAB_ROWS = 64


@obs_flight.kernel_annotation("quant.qdq_flat")
def _qdq_flat_impl(flat: jnp.ndarray, key: jax.Array, *, bits: int = 8,
                   bucket_elems: int = DEFAULT_BUCKET_ELEMS,
                   backend: str = "auto") -> jnp.ndarray:
    """Fused per-bucket Q(x) over a flat buffer (whole pytree, one pass).

    Bit-identical to decode_flat(encode_flat(flat, key)) — same uniform
    draws, same per-bucket params, same rounding. On the Pallas backend
    it is two passes over the caller's unpadded buffer and nothing else
    of its size: ``bucket_params``' stats kernel, then one coding kernel
    that draws each element's uniform in VMEM under its bucket's key
    (``bucket_keys``) and writes the dequantized values straight into the
    (N,) output. The jnp backend draws the uniforms in HBM over the
    bucket views of ``_bucket_views``."""
    if _use_pallas(backend):
        total = flat.size
        x = flat.reshape(-1).astype(jnp.float32)
        _, cap, nb, _, _ = flat_geometry(total, bits=bits,
                                         bucket_elems=bucket_elems)
        rows_b = cap // kernel.ROW
        block_r = _flat_block_r(_block_r(kernel.ROW, 8, 0), rows_b, nb)
        out = kernel.qdq_flat(
            _rows(x), bucket_keys(key, nb),
            bucket_params(x, bits=bits, bucket_elems=bucket_elems,
                          backend=backend),
            bits=bits, rows_b=rows_b, block_r=block_r,
            slab=math.gcd(SLAB_ROWS, block_r), interpret=_interpret())
        return out.reshape(-1)[:total].astype(flat.dtype)
    x4, u4, x3, u3, params, (pack, nb, _, rt, t) = _bucket_views(
        flat, key, bits=bits, bucket_elems=bucket_elems, backend=backend)
    head = None
    if nb > 1:
        head = ref.qdq_bucketed(x4, u4, params[:nb - 1, 0],
                                params[:nb - 1, 1], bits=bits).reshape(-1)
    lo, scale = params[nb - 1, 0], params[nb - 1, 1]
    tl = ref.decode(ref.encode(x3, u3, lo, scale, bits=bits), lo, scale)
    return _write_head_tail(head, tl.reshape(-1)[:t], (flat.size,),
                            flat.dtype)


qdq_flat = jax.jit(_qdq_flat_impl,
                   static_argnames=("bits", "bucket_elems", "backend"))

# Donating variant: the flat buffer's storage is handed to XLA for reuse
# as the (same shape/dtype) output. Safe ONLY when the caller's buffer is
# dead after the call — e.g. a hop's decode+add temporary, or a freshly
# flattened gradient; a no-op hint under an outer trace and on backends
# without donation (CPU), real HBM savings at top level on TPU.
qdq_flat_donated = jax.jit(_qdq_flat_impl,
                           static_argnames=("bits", "bucket_elems",
                                            "backend"),
                           donate_argnums=(0,))


def encode_flat_blocked(leaves, offsets, total: int, key, *, bits: int = 8,
                        bucket_elems: int = DEFAULT_BUCKET_ELEMS):
    """Cache-blocked whole-tree encode: the zero-copy pipeline's hot path.

    Instead of materializing the full flat buffer (flatten) and then
    streaming it again for stats + uniforms + encode — several DRAM
    round trips over the whole gradient — each bucket is assembled from
    its (statically known) leaf fragments into ONE bucket-sized hot
    buffer, and its (lo, scale), uniform draw, quantization, and packing
    all happen while that block is cache-resident. Leaves are read once,
    payload rows are written once; the only working buffer is one bucket.

    Bit-identical to ``encode_flat(flatten(tree))``: stats are exact
    min/max of the same elements, every bucket draws under
    ``bucket_key(key, b)``, and the math is the same jnp reference. (The
    Pallas tier keeps the full-buffer views — on TPU the bucketed grid
    is already the blocking.)

    ``leaves``/``offsets``/``total`` are the FlatLayout pieces (passed
    raw to keep this module independent of repro.core).
    """
    pack, cap, nb, rows_b, rows_kept = flat_geometry(
        total, bits=bits, bucket_elems=bucket_elems)
    granule = pack * LANES
    levels = (1 << bits) - 1
    flats = [leaf.reshape(-1).astype(jnp.float32) for leaf in leaves]
    sizes = [f.shape[0] for f in flats]
    payload = jnp.zeros((rows_kept, LANES), jnp.uint8)
    params = jnp.zeros((nb, 2), jnp.float32)
    row_off = 0
    for b in range(nb):
        start = b * cap
        belems = min(cap, total - start)
        buf = jnp.zeros((belems,), jnp.float32)
        for off, sz, fl in zip(offsets, sizes, flats):
            lo_e, hi_e = max(off, start), min(off + sz, start + belems)
            if lo_e < hi_e:
                buf = lax.dynamic_update_slice(
                    buf, fl[lo_e - off:hi_e - off], (lo_e - start,))
        lo = jnp.min(buf)
        hi = jnp.max(buf)
        scale = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
        rb = -(-belems // granule)
        if rb * granule != belems:
            buf = edge_pad(buf, rb * granule)
        x3 = buf.reshape(pack, rb, LANES)
        u = jax.random.uniform(bucket_key(key, b), x3.shape, jnp.float32)
        rows = ref.encode_packed(x3, u, lo, scale, bits=bits)
        payload = lax.dynamic_update_slice(payload, rows, (row_off, 0))
        params = lax.dynamic_update_slice(params, lo.reshape(1, 1), (b, 0))
        params = lax.dynamic_update_slice(params, scale.reshape(1, 1),
                                          (b, 1))
        row_off += rb
    return payload, params


@partial(jax.jit, static_argnames=("bits", "bucket_elems", "backend"))
@obs_flight.kernel_annotation("quant.encode_flat")
def encode_flat(flat: jnp.ndarray, key: jax.Array, *, bits: int = 8,
                bucket_elems: int = DEFAULT_BUCKET_ELEMS,
                backend: str = "auto"):
    """Bucketed encode of a flat fp32 buffer.

    Returns (payload uint8 (rows_kept, 512), params fp32 (n_buckets, 2)).
    Wire bytes = payload.nbytes + params.nbytes: the ONE message the
    fused exchanges ship per hop. Head and tail payload rows are written
    into one preallocated output (no concatenate — asserted via jaxpr in
    tests/test_flat_codec.py)."""
    x4, u4, x3, u3, params, (pack, nb, rows_b, rt, t) = _bucket_views(
        flat, key, bits=bits, bucket_elems=bucket_elems, backend=backend)
    head = None
    if _use_pallas(backend):
        if nb > 1:
            head = kernel.encode_packed_bucketed(
                x4, u4, params[:nb - 1], bits=bits,
                block_r=_block_r(LANES, 8 * pack + 1, pack),
                interpret=_interpret()).reshape(-1, LANES)
        tl = kernel.encode_packed(
            x3, u3, params[nb - 1:nb], bits=bits,
            block_r=_block_r(LANES, 8 * pack + 1, pack),
            interpret=_interpret())
    else:
        if nb > 1:
            head = ref.encode_packed_bucketed(
                x4, u4, params[:nb - 1, 0], params[:nb - 1, 1],
                bits=bits).reshape(-1, LANES)
        tl = ref.encode_packed(x3, u3, params[nb - 1, 0],
                               params[nb - 1, 1], bits=bits)
    rows_kept = (nb - 1) * rows_b + rt
    payload = _write_head_tail(head, tl, (rows_kept, LANES), jnp.uint8)
    return payload, params


def encode_partitioned_blocked(leaves, offsets, total: int, key, *,
                               n_parts: int, bits: int = 8,
                               bucket_elems: int = DEFAULT_BUCKET_ELEMS):
    """Cache-blocked partitioned whole-tree encode (the jnp tier of
    ``tree_encode_partitioned``).

    The vmapped flatten-then-encode pipeline materializes the full flat
    buffer and — worse — turns every per-partition dynamic_update_slice
    (edge_pad, head/tail assembly) into a full-buffer scatter under vmap,
    which is why the partitioned encode used to cost ~3x the flat encode.
    Here each partition's buckets are assembled straight from their
    (statically known) leaf fragments and statted/drawn/packed while
    cache-hot, exactly like ``encode_flat_blocked`` — leaves are read
    once, payload rows written once, no full-size temporary exists.

    Bit-identical to the vmapped ``_encode_partitions`` reference:
    partition p draws under fold_in(key, p), bucket b within it under
    ``bucket_key(fold_in(key, p), b)``, and positions past the real
    `total` repeat the LAST REAL element (edge_pad semantics), so they
    never perturb a bucket's (lo, hi). Partition sizes are granule-
    aligned, so no intra-bucket padding exists.

    Returns (payload (n_parts, rows_p, 512) uint8,
             params (n_parts, nb_p, 2) fp32).
    """
    part_elems, nb_p, rows_p = partition_geometry(
        total, n_parts, bits=bits, bucket_elems=bucket_elems)
    pack, cap, nb, _, _ = flat_geometry(part_elems, bits=bits,
                                        bucket_elems=bucket_elems)
    assert nb == nb_p, (nb, nb_p)
    granule = pack * LANES
    levels = (1 << bits) - 1
    flats = [leaf.reshape(-1).astype(jnp.float32) for leaf in leaves]
    sizes = [f.shape[0] for f in flats]
    last = flats[-1][-1]
    payload = jnp.zeros((n_parts, rows_p, LANES), jnp.uint8)
    params = jnp.zeros((n_parts, nb_p, 2), jnp.float32)
    for p in range(n_parts):
        pkey = bucket_key(key, p)   # fold_in(key, p): the partition key
        row_off = 0
        for b in range(nb):
            start = p * part_elems + b * cap
            belems = min(cap, part_elems - b * cap)
            buf = jnp.zeros((belems,), jnp.float32)
            for off, sz, fl in zip(offsets, sizes, flats):
                lo_e, hi_e = max(off, start), min(off + sz, start + belems)
                if lo_e < hi_e:
                    buf = lax.dynamic_update_slice(
                        buf, fl[lo_e - off:hi_e - off], (lo_e - start,))
            if start + belems > total:
                idx = jnp.arange(belems)
                buf = jnp.where(start + idx < total, buf, last)
            lo = jnp.min(buf)
            hi = jnp.max(buf)
            scale = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
            rb = belems // granule
            x3 = buf.reshape(pack, rb, LANES)
            u = jax.random.uniform(bucket_key(pkey, b), x3.shape,
                                   jnp.float32)
            rows = ref.encode_packed(x3, u, lo, scale, bits=bits)
            payload = lax.dynamic_update_slice(
                payload, rows.reshape(1, rb, LANES), (p, row_off, 0))
            params = lax.dynamic_update_slice(
                params, jnp.stack([lo, scale]).reshape(1, 1, 2), (p, b, 0))
            row_off += rb
    return payload, params


@partial(jax.jit, static_argnames=("bits", "total", "bucket_elems",
                                   "backend"))
@obs_flight.kernel_annotation("quant.decode_flat")
def decode_flat(payload: jnp.ndarray, params: jnp.ndarray, *, total: int,
                bits: int = 8, bucket_elems: int = DEFAULT_BUCKET_ELEMS,
                backend: str = "auto") -> jnp.ndarray:
    """Unpack + dequantize a bucketed wire payload back to (total,) fp32.

    Head and tail land in one preallocated output (single-buffer writes,
    no concatenate), mirroring encode_flat."""
    pack, cap, nb, rows_b, rows_kept = flat_geometry(
        total, bits=bits, bucket_elems=bucket_elems)
    head_rows = (nb - 1) * rows_b
    t = total - (nb - 1) * cap
    head = None
    if _use_pallas(backend):
        if nb > 1:
            head = kernel.decode_packed_bucketed(
                payload[:head_rows].reshape(nb - 1, rows_b, LANES),
                params[:nb - 1], bits=bits, out_dtype=jnp.float32,
                block_r=_block_r(LANES, 1 + 4, 1),
                interpret=_interpret()).reshape(-1)
        tl = kernel.decode_packed(
            payload[head_rows:], params[nb - 1:nb], bits=bits,
            out_dtype=jnp.float32, block_r=_block_r(LANES, 1 + 4, 1),
            interpret=_interpret())
    else:
        if nb > 1:
            head = ref.decode_packed_bucketed(
                payload[:head_rows].reshape(nb - 1, rows_b, LANES),
                params[:nb - 1, 0], params[:nb - 1, 1],
                bits=bits).reshape(-1)
        tl = ref.decode_packed(payload[head_rows:], params[nb - 1, 0],
                               params[nb - 1, 1], bits=bits)
    return _write_head_tail(head, tl.reshape(-1)[:t], (total,),
                            jnp.float32)


# ---------------------------------------------------------------------------
# Fused ring hop: decode + add + re-encode as ONE dispatch. The partitioned
# ring AllReduce's reduce-scatter hop is exactly this op over one partition.
# ---------------------------------------------------------------------------


def _dae_ref(payload, params, x4, u4, *, bits: int):
    """jnp reference for the fused hop: the literal decode -> add ->
    minmax -> encode composition on the (B, pack, Rb, C) bucket view."""
    levels = (1 << bits) - 1
    dec = ref.decode_packed_bucketed(payload, params[:, 0], params[:, 1],
                                     bits=bits)
    summed = dec + x4
    lo, hi = ref.minmax_bucketed(summed.reshape(summed.shape[0], -1))
    scale = jnp.where(hi > lo, (hi - lo) / levels, 1.0)
    out = ref.encode_packed_bucketed(summed, u4, lo, scale, bits=bits)
    return out, _stack2(lo, scale)


@partial(jax.jit, static_argnames=("bits", "bucket_elems", "backend"))
@obs_flight.kernel_annotation("quant.decode_add_encode_flat")
def decode_add_encode_flat(payload: jnp.ndarray, params: jnp.ndarray,
                           local: jnp.ndarray, key: jax.Array, *,
                           bits: int = 8,
                           bucket_elems: int = DEFAULT_BUCKET_ELEMS,
                           backend: str = "auto"):
    """ONE fused ring hop over a flat message: decode the packed payload,
    add the `local` fp32 buffer, and re-encode under `key`, without ever
    materializing the decoded or summed fp32 buffer (Pallas backend: the
    two-phase ``decode_add_encode_bucketed`` kernel; jnp backend: the
    composition reference). Bit-identical to

        encode_flat(decode_flat(payload, params, total=local.size)
                    + local, key)

    on both backends. Granule-aligned buffers (every ring partition, by
    ``partition_geometry`` construction) take the fused path; other sizes
    fall back to the sequential composition, whose edge-pad handling the
    fused kernel does not reproduce.
    """
    total = local.size
    pack, cap, nb, rows_b, rows_kept = flat_geometry(
        total, bits=bits, bucket_elems=bucket_elems)
    granule = pack * LANES
    flat = local.reshape(-1).astype(jnp.float32)
    if total % granule:
        dec = decode_flat(payload, params, total=total, bits=bits,
                          bucket_elems=bucket_elems, backend=backend)
        return encode_flat(dec + flat, key, bits=bits,
                           bucket_elems=bucket_elems, backend=backend)
    head_rows = (nb - 1) * rows_b
    head_elems = (nb - 1) * cap
    rt = rows_kept - head_rows
    use_pallas = _use_pallas(backend)
    head = head_p = None
    if nb > 1:
        x4 = flat[:head_elems].reshape(nb - 1, pack, rows_b, LANES)
        hkeys = jax.vmap(lambda b: bucket_key(key, b))(jnp.arange(nb - 1))
        u4 = jax.vmap(
            lambda k: jax.random.uniform(k, (pack, rows_b, LANES),
                                         jnp.float32))(hkeys)
        pay4 = payload[:head_rows].reshape(nb - 1, rows_b, LANES)
        if use_pallas:
            head, head_p = kernel.decode_add_encode_bucketed(
                pay4, params[:nb - 1], x4, u4, bits=bits,
                block_r=_block_r(LANES, 8 * pack + 2, pack),
                interpret=_interpret())
        else:
            head, head_p = _dae_ref(pay4, params[:nb - 1], x4, u4,
                                    bits=bits)
        head = head.reshape(-1, LANES)
    x3 = flat[head_elems:].reshape(1, pack, rt, LANES)
    u3 = jax.random.uniform(bucket_key(key, nb - 1),
                            (pack, rt, LANES),
                            jnp.float32).reshape(1, pack, rt, LANES)
    pay3 = payload[head_rows:].reshape(1, rt, LANES)
    if use_pallas:
        tl, tl_p = kernel.decode_add_encode_bucketed(
            pay3, params[nb - 1:nb], x3, u3, bits=bits,
            block_r=_block_r(LANES, 8 * pack + 2, pack),
            interpret=_interpret())
    else:
        tl, tl_p = _dae_ref(pay3, params[nb - 1:nb], x3, u3, bits=bits)
    out_payload = _write_head_tail(head, tl.reshape(rt, LANES),
                                   (rows_kept, LANES), jnp.uint8)
    out_params = _write_head_tail(head_p, tl_p, (nb, 2), jnp.float32)
    return out_payload, out_params
