"""Fused flat-buffer codec tier (the 'one packed message per exchange'
path): FlatLayout round trips, bucketed kernel equality across backends,
wire-byte savings vs the per-leaf reference, one-payload-per-hop ring
exchanges, and the per-message latency accounting in the cost models."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import communicators as C
from repro.core import compression, eventsim
from repro.kernels.quant import ops as q_ops

KEY = jax.random.PRNGKey(0)
AXIS = "w"


def _mixed_tree(n1=777, n2=95):
    """Mixed shapes/dtypes incl. odd sizes, a scalar, and a bf16 leaf."""
    k = jax.random.PRNGKey(42)
    return {
        "a": jax.random.normal(jax.random.fold_in(k, 0), (n1,)),
        "b": {"w": jax.random.normal(jax.random.fold_in(k, 1), (n2, 3)),
              "bf16": (jax.random.normal(jax.random.fold_in(k, 2), (33,))
                       .astype(jnp.bfloat16)),
              "scalar": jnp.float32(2.5)},
        "c": jax.random.normal(jax.random.fold_in(k, 3), (2, 5, 7)),
    }


def _assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x, np.float32), np.asarray(y, np.float32)), a, b)


# ------------------------------------------------------------ flat layout ----

@given(st.integers(min_value=1, max_value=4097),
       st.integers(min_value=1, max_value=600))
@settings(max_examples=12, deadline=None)
def test_flat_layout_round_trip(n1, n2):
    """unflatten(flatten(tree)) == tree bit-for-bit on mixed-shape /
    odd-size leaves (incl. bf16 and scalars)."""
    tree = _mixed_tree(n1, n2)
    layout = compression.FlatLayout.from_tree(tree)
    flat = layout.flatten(tree)
    assert flat.shape == (layout.total,) and flat.dtype == jnp.float32
    assert layout.total == sum(
        leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    out = layout.unflatten(flat)
    for l_in, l_out in zip(jax.tree_util.tree_leaves(tree),
                           jax.tree_util.tree_leaves(out)):
        assert l_in.shape == l_out.shape and l_in.dtype == l_out.dtype
        np.testing.assert_array_equal(np.asarray(l_in, np.float32),
                                      np.asarray(l_out, np.float32))


def test_flat_layout_offsets_are_static():
    tree = _mixed_tree()
    layout = compression.FlatLayout.from_tree(tree)
    # offsets are cumulative leaf sizes (the documented offset table)
    assert layout.offsets[0] == 0
    for i in range(1, layout.n_leaves):
        assert layout.offsets[i] == layout.offsets[i - 1] + layout.sizes[i - 1]
    # hashable (usable as static pytree aux / jit cache key)
    assert layout == compression.FlatLayout.from_tree(tree)
    hash(layout)


# --------------------------------------------------- bucketed kernel tier ----

@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("bucket_elems", [2048, 1 << 22])
def test_flat_backends_identical_and_roundtrip_equals_qdq(bits, bucket_elems):
    """Pallas (interpret) and jnp produce identical FlatPacked messages,
    and decode(encode(.)) == qdq(.) bit-for-bit through the fused tier —
    in both the multi-bucket and single-bucket regimes."""
    tree = _mixed_tree()
    pallas = compression.QuantCodec(bits, backend="pallas")
    jnp_ref = compression.QuantCodec(bits, backend="jnp")
    fp_p = pallas.tree_encode_flat(tree, KEY, bucket_elems=bucket_elems)
    fp_j = jnp_ref.tree_encode_flat(tree, KEY, bucket_elems=bucket_elems)
    np.testing.assert_array_equal(fp_p.payload, fp_j.payload)
    np.testing.assert_array_equal(fp_p.params, fp_j.params)
    # geometry: one (lo, scale) row per bucket
    total = compression.FlatLayout.from_tree(tree).total
    _, _, nb, _, rows_kept = q_ops.flat_geometry(
        total, bits=bits, bucket_elems=bucket_elems)
    assert fp_p.params.shape == (nb, 2)
    assert fp_p.payload.shape == (rows_kept, q_ops.LANES)
    # wire path == fused path, across backends
    _assert_trees_equal(pallas.tree_decode_flat(fp_p),
                        jnp_ref.tree_qdq_flat(tree, KEY,
                                              bucket_elems=bucket_elems))
    _assert_trees_equal(pallas.tree_qdq_flat(tree, KEY,
                                             bucket_elems=bucket_elems),
                        jnp_ref.tree_qdq_flat(tree, KEY,
                                              bucket_elems=bucket_elems))


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_bucket_params_match_per_bucket_reference(bits):
    """Each bucket's (lo, scale) row equals the per-leaf jnp reference's
    quant_params of that bucket's element slice — the fused tier is the
    per-leaf quantizer applied per contiguous bucket."""
    from repro.kernels.quant import ref

    tree = _mixed_tree(5000, 300)   # big enough for >1 bucket at all bits
    layout = compression.FlatLayout.from_tree(tree)
    flat = layout.flatten(tree)
    be = 2048
    fp = compression.QuantCodec(bits, backend="jnp").tree_encode_flat(
        tree, KEY, bucket_elems=be)
    _, cap, nb, _, _ = q_ops.flat_geometry(layout.total, bits=bits,
                                           bucket_elems=be)
    assert nb > 1   # exercise the grid-over-buckets path
    for b in range(nb):
        chunk = flat[b * cap: min((b + 1) * cap, layout.total)]
        lo, scale = ref.quant_params(chunk, bits)
        # lo is a pure min -> exact; scale may differ by 1 ulp between the
        # eager reference and the fused jit (XLA divide-by-constant), which
        # is why backend equality (above) is asserted WITHIN one trace
        np.testing.assert_array_equal(fp.params[b, 0], lo)
        np.testing.assert_allclose(fp.params[b, 1], scale, rtol=1e-6)


def test_flat_qdq_unbiased():
    """E[Q(x)] = x holds through the bucketed path (Assumption 3)."""
    cdc = compression.codec("rq4")
    x = jax.random.normal(KEY, (300,))
    keys = jax.random.split(jax.random.PRNGKey(1), 600)
    qs = jax.vmap(lambda k: cdc.flat_qdq(x, k, bucket_elems=128))(keys)
    assert float(jnp.abs(qs.mean(0) - x).max()) < 0.6


# (elements, bucket_elems): one bucket; one bucket with repro-100m's
# remainder (N mod 1024 = 768); three buckets, the same remainder; and a
# tail bucket of 100 elements, shorter than one 128-lane row
_FLAT_SHAPES = [(777, 1 << 22), (3 * 1024 + 768, 1 << 22),
                (5 * 1024 + 768, 2048), (2 * 2048 + 100, 2048)]


@pytest.mark.parametrize("bits", [8, 4, 2])
@pytest.mark.parametrize("n,bucket_elems", _FLAT_SHAPES)
def test_pallas_qdq_flat_bit_identical(bits, n, bucket_elems):
    """The Pallas qdq_flat (unpadded buffer, uniforms drawn in the kernel)
    equals the jnp backend and decode_flat(encode_flat(.)) bit for bit."""
    x = jax.random.normal(jax.random.PRNGKey(n), (n,)) * 3.0
    key = jax.random.PRNGKey(bits)
    kw = dict(bits=bits, bucket_elems=bucket_elems)
    got = q_ops.qdq_flat(x, key, backend="pallas", **kw)
    payload, params = q_ops.encode_flat(x, key, backend="jnp", **kw)
    for want in (q_ops.qdq_flat(x, key, backend="jnp", **kw),
                 q_ops.decode_flat(payload, params, total=n, backend="jnp",
                                   **kw)):
        np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                      np.asarray(want).view(np.uint32))


def test_in_kernel_draw_is_jax_random_uniform():
    """kernel.threefry_uniform(fold_in(key, b), c) is element c of
    jax.random.uniform(fold_in(key, b), (pack, rows, 512)) bit for bit —
    at the first and last counter of a 4Mi-element bucket, and across a
    bucket boundary of the flat buffer."""
    from repro.kernels.quant import kernel

    assert jax.config.jax_threefry_partitionable   # the formula needs it

    def drawn(keys, b, c):
        k = keys[b]
        return np.asarray(kernel.threefry_uniform(
            k[..., 0], k[..., 1], jnp.asarray(c, jnp.int32))).view(np.uint32)

    cap = 1 << 22
    ends = np.array([0, 1, 511, 512, 1 << 21, cap - 2, cap - 1])
    for seed in (0, 7, 2 ** 31 + 5):
        key = jax.random.PRNGKey(seed)
        keys = q_ops.bucket_keys(key, 3)
        for bits in (8, 4, 2):
            pack = 8 // bits
            u = jax.random.uniform(q_ops.bucket_key(key, 2),
                                   (pack, cap // (pack * 512), 512),
                                   jnp.float32)
            np.testing.assert_array_equal(
                drawn(keys, np.full(ends.shape, 2), ends),
                np.asarray(u).reshape(-1)[ends].view(np.uint32))
        p = np.arange(cap - 64, cap + 64)       # bucket 0 -> bucket 1
        want = np.concatenate([
            np.asarray(jax.random.uniform(q_ops.bucket_key(key, b),
                                          (1, cap // 512, 512)))
            .reshape(-1)[sl] for b, sl in ((0, slice(-64, None)),
                                            (1, slice(0, 64)))])
        np.testing.assert_array_equal(drawn(keys, p // cap, p % cap),
                                      want.view(np.uint32))


def test_bucket_keys_refuse_draws_the_kernel_cannot_match():
    """Off jax_threefry_partitionable, jax.random.uniform draws other bits
    than the kernel's formula: the Pallas path refuses to run."""
    was = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", False)
    try:
        with pytest.raises(ValueError, match="partitionable"):
            q_ops.bucket_keys(KEY, 2)
    finally:
        jax.config.update("jax_threefry_partitionable", was)


# -------------------------------------------------------------- wire bytes ---

@pytest.mark.parametrize("name,bits", [("rq8", 8), ("rq4", 4), ("rq2", 2)])
def test_fused_wire_bytes_beat_per_leaf(name, bits):
    """Fused pays <= 1 pad granule + one 8B params row per bucket; the
    per-leaf path pays up to one granule + one row per LEAF. Asserted
    against the exact wire-format arithmetic."""
    tree = {f"l{i}": jnp.zeros((100 + 13 * i,), jnp.float32)
            for i in range(40)}
    cdc = compression.codec(name)
    fused = cdc.tree_wire_bytes_flat(tree)
    per_leaf = cdc.tree_wire_bytes(tree)
    assert fused < per_leaf
    total = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    pack = 8 // bits
    granule = pack * 512
    _, _, nb, _, rows_kept = q_ops.flat_geometry(total, bits=bits)
    # exact: fused = kept payload rows + one params row per bucket
    assert fused == rows_kept * 512 + nb * 8
    # bound: whole-tree payload <= ideal + ONE pad granule's bytes
    assert fused <= total * bits / 8 + granule * bits / 8 + nb * 8
    # per-leaf = sum of per-leaf granule-padded payloads + L headers
    want_leafwise = sum(
        -(-leaf.size // granule) * 512 + 8
        for leaf in jax.tree_util.tree_leaves(tree))
    assert per_leaf == want_leafwise


def test_repro_100m_fused_wire_bytes_strictly_lower():
    """Acceptance: measured wire bytes for the repro-100m gradient tree
    are strictly lower fused than per-leaf, by exactly the padding +
    params-header savings (eval_shape only — nothing is allocated)."""
    from repro import configs
    from repro.models import transformer

    cfg = configs.get_config("repro-100m")
    grads = jax.eval_shape(
        lambda: transformer.init(cfg, jax.random.PRNGKey(0)))
    leaves = jax.tree_util.tree_leaves(grads)
    total = sum(leaf.size for leaf in leaves)
    for name, bits in (("rq8", 8), ("rq4", 4), ("rq2", 2)):
        cdc = compression.codec(name)
        fused = cdc.tree_wire_bytes_flat(grads)
        per_leaf = cdc.tree_wire_bytes(grads)
        assert fused < per_leaf
        # the saving is exactly (per-leaf padding - fused padding) +
        # (L - n_buckets) params headers
        granule = (8 // bits) * 512
        _, _, nb, _, rows_kept = q_ops.flat_geometry(total, bits=bits)
        leaf_rows = sum(-(-leaf.size // granule) for leaf in leaves)
        pad_saving = (leaf_rows - rows_kept) * 512
        header_saving = (len(leaves) - nb) * 8
        assert per_leaf - fused == pad_saving + header_saving
        assert header_saving > 0   # far fewer params rows than leaves


# --------------------------------------------------------- fused exchanges ---

def _count_ppermute_calls(fn, *args):
    """Trace fn and count lax.ppermute call sites (the fori_loop hop body
    traces exactly once, so this is arrays shipped per hop)."""
    from jax import lax

    calls = {"n": 0}
    real = lax.ppermute

    def counting(x, axis_name, perm):
        calls["n"] += 1
        return real(x, axis_name, perm)

    C.lax.ppermute = counting
    try:
        jax.make_jaxpr(fn)(*args)
    finally:
        C.lax.ppermute = real
    return calls["n"]


def test_ring_ships_one_packed_payload_per_hop():
    """Per-hop array counts are leaf-count independent on both fused
    tiers: the partitioned ring ppermutes one partition payload + one
    partition header in EACH of its two phases (reduce-scatter +
    all-gather = 4 call sites); the monolithic chain ships one FlatPacked
    (2 call sites); the per-leaf reference ships 2 arrays per leaf."""
    n = 4
    tree = {f"l{i}": jax.random.normal(jax.random.fold_in(KEY, i),
                                       (n, 17 + i)) for i in range(5)}
    key = jax.random.PRNGKey(1)

    def run(ex):
        return lambda g: jax.vmap(
            lambda gg: ex(gg, (), key, axis_name=AXIS)[0],
            axis_name=AXIS)(g)

    partitioned = _count_ppermute_calls(
        run(C.CSGDRingExchange(compressor="rq4")), tree)
    assert partitioned == 4    # (payload, params) x two phases
    mono = _count_ppermute_calls(
        run(C.CSGDRingExchange(compressor="rq4", partitioned=False)), tree)
    assert mono == 2           # one payload + one (n_buckets, 2) header
    per_leaf = _count_ppermute_calls(
        run(C.CSGDRingExchange(compressor="rq4", flat=False)), tree)
    assert per_leaf == 2 * 5   # one (payload, params) pair per leaf


def test_csgd_ring_monolithic_matches_manual_flat_chain():
    """The monolithic chain (partitioned=False: ONE FlatPacked through
    ppermute, N-1 full hops) equals the flat-qdq chain formulation,
    because flat decode(encode(.)) == flat qdq. This is the reference
    the partitioned tier's per-partition chains are compared against —
    both satisfy Eq. (3.3)'s recursion, with different nesting orders."""
    n = 4
    g = {"a": jax.random.normal(KEY, (n, 33)),
         "b": jax.random.normal(jax.random.fold_in(KEY, 9), (n, 7, 5))}
    key = jax.random.PRNGKey(1)
    ex = C.CSGDRingExchange(compressor="rq4", partitioned=False)
    out, _ = jax.vmap(lambda gg: ex(gg, (), key, axis_name=AXIS),
                      axis_name=AXIS)(g)

    cdc = compression.codec("rq4")
    gi = lambda i: jax.tree_util.tree_map(lambda leaf: leaf[i], g)
    layout = compression.FlatLayout.from_tree(gi(0))
    accs = [cdc.flat_qdq(layout.flatten(gi(i)), jax.random.fold_in(key, i))
            for i in range(n)]
    for h in range(1, n):
        prev = list(accs)
        accs = [cdc.flat_qdq(
            prev[(i - 1) % n] + layout.flatten(gi(i)),
            jax.random.fold_in(jax.random.fold_in(key, i), h))
            for i in range(n)]
    for i in range(n):
        expect = layout.unflatten(accs[i] / n)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a)[i], np.asarray(b), rtol=1e-6, atol=1e-6),
            out, expect)


# ------------------------------------------------- partitioned ring tier ----

def _partition_reference_chains(tree, key, n, codec="rq4"):
    """Eq. (3.3) applied per partition: partition p's chain starts at
    worker p (key fold_in(key, p)) and is requantized at each of the
    n-1 downstream workers (key fold_in(fold_in(key, w), h)). Returns
    the (n, part_elems) finished partitions and the layout."""
    from repro.kernels.quant import ops as q

    cdc = compression.codec(codec)
    gi = lambda i: jax.tree_util.tree_map(lambda leaf: leaf[i], tree)
    layout = compression.FlatLayout.from_tree(gi(0))
    pe, _, _ = cdc.partition_geometry(layout.total, n)
    gparts = [np.asarray(q.edge_pad(layout.flatten(gi(i)),
                                    n * pe)).reshape(n, pe)
              for i in range(n)]
    final = np.zeros((n, pe), np.float32)
    for p in range(n):
        acc = cdc.flat_qdq(jnp.asarray(gparts[p][p]),
                           jax.random.fold_in(key, p))
        for h in range(1, n):
            w = (p + h) % n
            acc = cdc.flat_qdq(acc + jnp.asarray(gparts[w][p]),
                               jax.random.fold_in(
                                   jax.random.fold_in(key, w), h))
        final[p] = np.asarray(acc)
    return final, layout, pe


def test_partitioned_ring_chains_bit_exact_and_verbatim():
    """Acceptance for the partitioned ring: (a) every partition equals
    the per-partition reference chain BIT-FOR-BIT on that slice —
    Figure 3.3's chains, built from the same flat_qdq the monolithic
    reference uses; (b) the all-gather ships finished partitions
    verbatim, so all workers end bit-identical (no re-quantization
    drift) — unlike the monolithic chain's per-worker nesting orders."""
    n = 4
    tree = {"a": jax.random.normal(KEY, (n, 33)),
            "b": jax.random.normal(jax.random.fold_in(KEY, 9), (n, 7, 5))}
    key = jax.random.PRNGKey(1)
    ex = C.CSGDRingExchange(compressor="rq4")
    out, _ = jax.vmap(lambda gg: ex(gg, (), key, axis_name=AXIS),
                      axis_name=AXIS)(tree)

    # (b) verbatim all-gather: bit-identical result on every worker
    for leaf in jax.tree_util.tree_leaves(out):
        for i in range(1, n):
            np.testing.assert_array_equal(np.asarray(leaf[0]),
                                          np.asarray(leaf[i]))

    # (a) per-partition chains, bit-for-bit
    final, layout, pe = _partition_reference_chains(tree, key, n)
    expect = layout.unflatten(
        jnp.asarray(final.reshape(-1)[: layout.total] / n))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a)[0], np.asarray(b)), out, expect)


def test_partitioned_roundtrip_equals_qdq_per_bucket_both_backends():
    """decode(encode(x)) == qdq(x) per bucket holds through the
    partitioned path on both backends, and the two backends produce
    identical PartitionedFlatPacked bits."""
    from repro.kernels.quant import ops as q

    tree = _mixed_tree(5000, 300)
    n_parts = 4
    for bits in (8, 4, 2):
        pallas = compression.QuantCodec(bits, backend="pallas")
        jnp_ref = compression.QuantCodec(bits, backend="jnp")
        pp = pallas.tree_encode_partitioned(tree, KEY, n_parts,
                                            bucket_elems=2048)
        pj = jnp_ref.tree_encode_partitioned(tree, KEY, n_parts,
                                             bucket_elems=2048)
        np.testing.assert_array_equal(pp.payload, pj.payload)
        np.testing.assert_array_equal(pp.params, pj.params)
        # per-partition decode == per-partition qdq (same fold_in keys)
        layout = compression.FlatLayout.from_tree(tree)
        pe = pp.part_elems
        padded = q.edge_pad(layout.flatten(tree), n_parts * pe)
        dec = pallas.flat_decode_partitioned(pp)
        for p in range(n_parts):
            want = q.qdq_flat(padded[p * pe:(p + 1) * pe],
                              jax.random.fold_in(KEY, p), bits=bits,
                              bucket_elems=2048, backend="jnp")
            got = np.asarray(dec[p * pe:min((p + 1) * pe, layout.total)])
            np.testing.assert_array_equal(got,
                                          np.asarray(want)[:got.shape[0]])


def test_partitioned_ring_wire_bytes_bandwidth_optimal():
    """Acceptance: per-worker wire bytes = 2*M*(N-1)/N within one pad
    granule (+ params rows) per partition, exactly reproducible from the
    partition geometry, and strictly below the monolithic (N-1)*M."""
    from repro.kernels.quant import ops as q

    tree = {f"l{i}": jnp.zeros((3000 + 13 * i,), jnp.float32)
            for i in range(25)}
    total = sum(leaf.size for leaf in jax.tree_util.tree_leaves(tree))
    for name, bits in (("rq8", 8), ("rq4", 4), ("rq2", 2)):
        for n in (2, 4, 8):
            ex = C.CSGDRingExchange(compressor=name)
            got = ex.message_bytes(tree, n_workers=n)
            pe, nb_p, rows_p = q.partition_geometry(total, n, bits=bits)
            # exact, from the geometry
            assert got == 2 * (n - 1) * (rows_p * 512 + nb_p * 8)
            # bandwidth-optimal bound: ideal payload 2*M*(n-1)/n, plus at
            # most one pad granule (512 payload B) + header per partition
            ideal = 2 * (n - 1) / n * (total * bits / 8)
            assert got >= ideal
            assert got <= ideal + 2 * (n - 1) * (512 + nb_p * 8)
            # strictly below the monolithic chain for n > 2
            mono = C.CSGDRingExchange(
                compressor=name, partitioned=False).message_bytes(
                    tree, n_workers=n)
            if n > 2:
                assert got < mono
            assert ex.n_wire_messages(n) == 2 * (n - 1)


def test_flat_layout_from_tree_is_cached():
    """Satellite: FlatLayout.from_tree memoizes on (treedef, shapes,
    dtypes) — repeat calls return the SAME object instead of rebuilding
    the offset table every trace."""
    tree = _mixed_tree()
    l1 = compression.FlatLayout.from_tree(tree)
    l2 = compression.FlatLayout.from_tree(tree)
    assert l1 is l2
    # different static structure -> different layout
    other = {"x": jnp.zeros((7,))}
    assert compression.FlatLayout.from_tree(other) is not l1


def _jaxpr_primitives(closed) -> set:
    acc = set()

    def rec(jaxpr):
        for e in jaxpr.eqns:
            acc.add(e.primitive.name)
            for v in e.params.values():
                if hasattr(v, "eqns"):
                    rec(v)
                elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):
                    rec(v.jaxpr)

    rec(closed.jaxpr)
    return acc


def test_fused_encode_jaxpr_has_no_concatenate():
    """Acceptance: the whole fused pipeline — flatten, stats, encode,
    qdq, decode — contains NO concatenate op anywhere in its jaxpr; head
    and tail are single-buffer dynamic_update_slice writes. (This is the
    op-count form of the perf assertion: the PR-2 regression came from
    flatten->concatenate->pad->re-concatenate materializing the buffer
    several times per encode.)"""
    tree = _mixed_tree(5000, 300)
    layout = compression.FlatLayout.from_tree(tree)
    key = KEY
    for backend in ("jnp", "pallas"):
        cdc = compression.QuantCodec(4, backend=backend)

        enc = jax.make_jaxpr(
            lambda t, k: cdc.tree_encode_flat(t, k, bucket_elems=2048))(
                tree, key)
        prims = _jaxpr_primitives(enc)
        assert "concatenate" not in prims, sorted(prims)
        assert "dynamic_update_slice" in prims

        qdq = jax.make_jaxpr(
            lambda t, k: cdc.tree_qdq_flat(t, k, bucket_elems=2048))(
                tree, key)
        assert "concatenate" not in _jaxpr_primitives(qdq)

        fp = cdc.tree_encode_flat(tree, key, bucket_elems=2048)
        dec = jax.make_jaxpr(cdc.tree_decode_flat)(fp)
        assert "concatenate" not in _jaxpr_primitives(dec)


@pytest.mark.skipif(not os.environ.get("RUN_PERF_TESTS"),
                    reason="timing on CI CPU is too noisy — the jaxpr "
                           "op-count test above is the CI-stable form; "
                           "set RUN_PERF_TESTS=1 to run")
def test_fused_steady_state_not_slower_than_per_leaf():
    """Satellite (timing form): fused steady-state tree-encode is no
    slower than per-leaf on the repro-100m gradient tree — the PR-2
    flat-path regression stays dead. BENCH_kernels.json carries the
    committed measurement (flat_vs_perleaf_speedup >= 1)."""
    import time

    from benchmarks.kernels_bench import _grad_tree

    grads = _grad_tree(smoke=True)
    cdc = compression.codec("rq8")
    key = KEY

    def best_of(fn, k=3):
        jax.block_until_ready(fn())      # warm-up / compile
        best = float("inf")
        for _ in range(k):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best = min(best, time.perf_counter() - t0)
        return best

    t_leaf = best_of(lambda: cdc.tree_encode(grads, key))
    t_flat = best_of(lambda: cdc.tree_encode_flat(grads, key))
    assert t_flat <= t_leaf * 1.1   # 10% noise floor


def test_ecsgd_flat_state_is_single_buffer():
    """flat=True carries ONE flat fp32 residual per side, and the Lemma
    3.4.1 recursion still holds on a multi-leaf tree."""
    n = 4
    params = {"a": jnp.zeros((24,)), "b": jnp.zeros((3, 5))}
    ex = C.ECSGDExchange(compressor="sign1")
    state = ex.init(params)
    total = compression.FlatLayout.from_tree(params).total
    assert state["worker_err"].shape == (total,)
    assert state["server_err"].shape == (total,)

    # Lemma 3.4.1 on the flat recursion: x~ follows plain averaged SGD
    lr, steps = 0.1, 5
    key = jax.random.PRNGKey(0)
    state = jax.vmap(ex.init)(
        jax.tree_util.tree_map(
            lambda p: jnp.broadcast_to(p[None], (n,) + p.shape), params))
    layout = compression.FlatLayout.from_tree(params)
    x = jnp.zeros((total,))
    x_tilde = x.copy()
    for t in range(steps):
        g = jax.random.normal(jax.random.fold_in(key, t), (n, total))
        gtree = jax.vmap(layout.unflatten)(g)
        out, state = jax.vmap(
            lambda gg, s: ex(gg, s, jax.random.fold_in(key, 100 + t),
                             axis_name=AXIS), axis_name=AXIS)(gtree, state)
        out0 = layout.flatten(
            jax.tree_util.tree_map(lambda leaf: leaf[0], out))
        x = x - lr * out0
        omega = state["server_err"][0] + state["worker_err"].mean(0)
        x_tilde = x_tilde - lr * g.mean(0)
        np.testing.assert_allclose(x - lr * omega, x_tilde, rtol=1e-4,
                                   atol=1e-5)


def test_make_exchange_gossip_registered():
    """Satellite: make_exchange('gossip', topology=...) works like every
    other pattern."""
    assert "gossip" in C.EXCHANGES
    gm = C.make_exchange("gossip", topology="full")
    assert isinstance(gm, C.GossipMix) and gm.topology == "full"
    n = 4
    x = jax.random.normal(KEY, (n, 6))
    mixed = jax.vmap(lambda xi: gm(xi, axis_name=AXIS), axis_name=AXIS)(x)
    np.testing.assert_allclose(
        np.asarray(mixed), np.broadcast_to(np.asarray(x).mean(0), (n, 6)),
        rtol=1e-5)
    ring = C.make_exchange("gossip", topology="ring")
    assert ring.topology == "ring"


def test_exchange_message_bytes_fused_lower_on_multi_leaf_tree():
    """Default (flat) exchanges report the fused message size, strictly
    below the per-leaf reference on a many-leaf tree."""
    tree = {f"l{i}": jnp.zeros((1000 + i,), jnp.float32) for i in range(20)}
    for flat_ex, leaf_ex in [
            (C.CSGDRingExchange(compressor="rq4"),
             C.CSGDRingExchange(compressor="rq4", flat=False)),
            (C.CSGDPSExchange(compressor="rq4"),
             C.CSGDPSExchange(compressor="rq4", flat=False)),
            (C.ECSGDExchange(compressor="rq4"),
             C.ECSGDExchange(compressor="rq4", flat=False))]:
        assert flat_ex.message_bytes(tree, n_workers=4) < \
            leaf_ex.message_bytes(tree, n_workers=4)
    # non-packable codec: ONE spec header instead of one per leaf
    sign = compression.codec("sign1")
    total = sum(l.size for l in jax.tree_util.tree_leaves(tree))
    assert sign.tree_wire_bytes_flat(tree) == \
        sign.spec.compressed_bytes(total)
    assert sign.tree_wire_bytes_flat(tree) < sign.tree_wire_bytes(tree)


# ------------------------------------------------------ cost-model users -----

def test_eventsim_per_message_latency_accounting():
    """n_messages multiplies the latency term only (transfer bytes are
    unchanged): the fused-vs-per-leaf gap is 2(n-1)(L-1) t_lat on the
    ring — the paper's §1.3 argument, now measurable."""
    n, lat, tr, size, L = 8, 1e-3, 1e-2, 100.0, 110
    fused = eventsim.ring_allreduce_makespan(n, size, t_lat=lat, t_tr=tr,
                                             n_messages=1)
    leafwise = eventsim.ring_allreduce_makespan(n, size, t_lat=lat,
                                                t_tr=tr, n_messages=L)
    assert leafwise - fused == pytest.approx(2 * (n - 1) * (L - 1) * lat)
    # transfer term identical
    assert fused - 2 * (n - 1) * lat == pytest.approx(
        leafwise - 2 * (n - 1) * L * lat)
    # same semantics in the discrete-event simulator itself
    d1 = eventsim.simulate([eventsim.Msg(0.0, 0, 1, size, "m", 1)],
                           t_lat=lat, t_tr=tr)
    dL = eventsim.simulate([eventsim.Msg(0.0, 0, 1, size, "m", L)],
                           t_lat=lat, t_tr=tr)
    assert dL.makespan - d1.makespan == pytest.approx((L - 1) * lat)
    # and in the PS / multi-PS / decentralized closed forms
    for fn in (eventsim.single_ps_makespan, eventsim.multi_ps_makespan,
               eventsim.decentralized_makespan):
        assert fn(n, size, t_lat=lat, t_tr=tr, n_messages=L) > \
            fn(n, size, t_lat=lat, t_tr=tr, n_messages=1)


def test_table1_1_fused_vs_per_leaf_block():
    """The benchmark's three-tier ring comparison exposes the per-message
    latency gap, the wire-byte saving, AND the partitioned tier's
    2M(N-1)/N accounting on a real gradient tree."""
    from benchmarks.table1_1 import fused_vs_per_leaf

    n = 8
    f = fused_vs_per_leaf(n_workers=n)
    assert f["n_leaves"] > 50
    assert f["fused_bytes"] < f["per_leaf_bytes"]
    # monolithic chains: n-1 hops, per-leaf pays (L-1) extra t_lat each
    assert f["latency_gap_s"] == pytest.approx(
        (n - 1) * (f["n_leaves"] - 1) * 1e-3)
    # acceptance: partitioned per-worker wire bytes == 2(n-1) partition
    # messages == 2*M*(n-1)/n up to one pad granule + header/partition,
    # and the table reports 2(n-1) wire messages per iteration
    assert f["n_wire_messages"] == 2 * (n - 1)
    assert f["partitioned_wire_bytes"] == \
        2 * (n - 1) * f["partitioned_part_bytes"]
    ideal = 2 * (n - 1) / n * (f["size_mb"] * 1e6 / 8)   # rq4: bits/8=0.5
    assert ideal <= f["partitioned_wire_bytes"] <= ideal * 1.01
    assert f["partitioned_wire_bytes"] < f["mono_wire_bytes"]
    assert f["partitioned_makespan_s"] < f["fused_makespan_s"]
