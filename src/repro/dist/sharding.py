"""Sharding rules for the production (pjit) tier.

One place holds every placement decision:

  * params  — FSDP + tensor parallelism by parameter *name*:
      - matmul weights (d_in, d_out): column-parallel P('data', 'model')
        by default; output/down projections are row-parallel
        P('model', 'data') so the block needs exactly one all-reduce;
      - stacked banks (scan_blocks layer stacks, MoE expert banks) carry
        leading replicated dims and shard their input dim over ALL
        data-like axes (('pod', 'data') on the multi-pod mesh) — these are
        the dominant parameters, so they take the widest FSDP axis set;
      - the embedding table is fully sharded P('model', 'data'); the
        activations it produces are re-pinned by `constrain_act` (stops
        XLA propagating the table layout into token-replicated
        activations);
      - vectors (norm scales, biases) are replicated.
  * batches — leading batch dim over the activation batch axes
    (set_activation_batch_axes; ('data',) single-pod, ('pod', 'data')
    multi-pod), skipped when the dim does not divide.
  * caches  — (batch, seq, heads, head_dim) KV layouts shard batch by
    'data' and heads by 'model', falling back to head_dim when the head
    count does not divide the model axis (GQA with few KV heads).

Every rule degrades to replication when a dim does not divide the axis —
`_maybe` is the single divisibility gate, so a 1x1 test mesh exercises
the full rule logic without constraining anything.
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

# Activation-batch axes: ('data',) single-pod, ('pod', 'data') multi-pod.
# Stacked parameter banks reuse this tuple as their FSDP axis set.
_ACT_BATCH_AXES: tuple = ("data",)

# Modules whose 2D weight is row-parallel (contracting dim sharded by
# 'model'): attention/mixer output projections and MLP down projections.
_ROW_PARALLEL = ("o", "down", "out")

# MoE expert banks: (n_experts, d_in, d_out) with the expert dim replicated.
_MOE_COL = ("w_gate", "w_up")
_MOE_ROW = ("w_down",)


def set_activation_batch_axes(axes: Sequence[str]) -> None:
    """Declare the mesh axes that carry the batch dim of activations."""
    global _ACT_BATCH_AXES
    _ACT_BATCH_AXES = tuple(axes)


def _axis_sizes(mesh) -> dict:
    return dict(mesh.shape)


def _maybe(axis, dim: int, mesh):
    """`axis` if `dim` divides its mesh size, else None (replicate).

    `axis` may be a single name or a tuple of names (product of sizes);
    names absent from the mesh always replicate.
    """
    if axis is None:
        return None
    sizes = _axis_sizes(mesh)
    names = axis if isinstance(axis, tuple) else (axis,)
    total = 1
    for a in names:
        if a not in sizes:
            return None
        total *= sizes[a]
    return axis if total > 0 and dim % total == 0 else None


def _path_names(path) -> tuple:
    """Key path (DictKey/SequenceKey/GetAttrKey/...) -> tuple of names."""
    names = []
    for p in path:
        if hasattr(p, "key"):
            names.append(str(p.key))
        elif hasattr(p, "name"):
            names.append(str(p.name))
        elif hasattr(p, "idx"):
            names.append(str(p.idx))
        else:
            names.append(str(p))
    return tuple(names)


# --------------------------------------------------------------------------
# Params
# --------------------------------------------------------------------------


def param_spec(path, shape: tuple, mesh) -> P:
    """PartitionSpec for one parameter leaf, keyed by its tree path."""
    names = _path_names(path)
    leaf = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""

    if leaf == "embed" and len(shape) == 2:
        # fully sharded table: vocab x model, features x data (FSDP)
        return P(_maybe("model", shape[0], mesh),
                 _maybe("data", shape[1], mesh))

    if leaf in _MOE_COL + _MOE_ROW and len(shape) >= 3:
        lead = (None,) * (len(shape) - 2)
        din, dout = shape[-2], shape[-1]
        if leaf in _MOE_ROW:
            return P(*lead, _maybe("model", din, mesh),
                     _maybe(_ACT_BATCH_AXES, dout, mesh))
        return P(*lead, _maybe(_ACT_BATCH_AXES, din, mesh),
                 _maybe("model", dout, mesh))

    if len(shape) >= 2:
        lead = (None,) * (len(shape) - 2)
        din, dout = shape[-2], shape[-1]
        # stacked (scan) params shard over the full data-axis tuple; plain
        # 2D weights use the bare 'data' axis
        dax = _ACT_BATCH_AXES if lead else "data"
        row = parent in _ROW_PARALLEL or (parent == "v" and "ffn" in names)
        if row:
            return P(*lead, _maybe("model", din, mesh),
                     _maybe(dax, dout, mesh))
        return P(*lead, _maybe(dax, din, mesh), _maybe("model", dout, mesh))

    return P()   # vectors / scalars replicate


def params_shardings_leaf(path, leaf, mesh) -> NamedSharding:
    return NamedSharding(mesh, param_spec(path, leaf.shape, mesh))


def params_shardings(params, mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: params_shardings_leaf(p, l, mesh), params)


# --------------------------------------------------------------------------
# Batches and activations
# --------------------------------------------------------------------------


def batch_spec(shape: tuple, mesh) -> P:
    """Leading dim over the activation batch axes; everything else replicated."""
    if not shape:
        return P()
    return P(_maybe(_ACT_BATCH_AXES, shape[0], mesh),
             *(None,) * (len(shape) - 1))


def batch_shardings(batch, mesh):
    return jax.tree_util.tree_map(
        lambda l: NamedSharding(mesh, batch_spec(l.shape, mesh)), batch)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _ctx_mesh() -> Optional[Any]:
    """The (abstract) mesh installed by an enclosing ``jax.set_mesh``."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def on_every_device(fn):
    """``fn`` run whole on every device of the context mesh, on replicated
    operands (a ``shard_map`` with replicated specs); ``fn`` itself
    off-mesh. XLA cannot partition a Pallas (Mosaic) kernel, so a kernel
    call inside a jit over the mesh goes through this."""
    mesh = _ctx_mesh()
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def constrain_act(x):
    """Pin an activation's batch-dim sharding inside jit (no-op off-mesh)."""
    mesh = _ctx_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, batch_spec(x.shape, mesh))


def constrain_heads(x):
    """Pin a (batch, seq, heads, head_dim) activation: batch over the data
    axes, heads over 'model' (head_dim fallback for narrow GQA)."""
    mesh = _ctx_mesh()
    if mesh is None or x.ndim != 4:
        return x
    b, _, h, dh = x.shape
    ba = _maybe(_ACT_BATCH_AXES, b, mesh)
    if _maybe("model", h, mesh):
        spec = P(ba, None, "model", None)
    elif _maybe("model", dh, mesh):
        spec = P(ba, None, None, "model")
    else:
        spec = P(ba, None, None, None)
    return jax.lax.with_sharding_constraint(x, spec)


# --------------------------------------------------------------------------
# Decode-state caches
# --------------------------------------------------------------------------


def cache_spec(path, shape: tuple, mesh) -> P:
    """KV caches (batch, seq, heads, head_dim): batch x 'data', heads x
    'model' with head_dim fallback; other state leaves shard batch only."""
    del path
    if len(shape) == 4:
        b, _, h, dh = shape
        ba = _maybe("data", b, mesh)
        if _maybe("model", h, mesh):
            return P(ba, None, "model", None)
        if _maybe("model", dh, mesh):
            return P(ba, None, None, "model")
        return P(ba, None, None, None)
    if not shape:
        return P()
    return P(_maybe("data", shape[0], mesh), *(None,) * (len(shape) - 1))


def cache_shardings(state, mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, cache_spec(p, l.shape, mesh)),
        state)
