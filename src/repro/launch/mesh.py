"""Production meshes.

Target hardware: TPU v5e pods, 256 chips/pod (16x16). Mesh axes:
  single-pod:  (16, 16)    ('data', 'model')
  multi-pod:   (2, 16, 16) ('pod', 'data', 'model')  — 512 chips

`make_production_mesh` is a FUNCTION (not a module constant) so importing
this module never initializes jax's device backend; the dry-run launcher
sets --xla_force_host_platform_device_count=512 before first jax use.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple, axes: tuple, *, devices=None):
    """The repo's one mesh constructor: every axis is ``AxisType.Auto``.

    ``jax.make_mesh`` alone makes Explicit axes (jax 0.9), under which
    ``with_sharding_constraint`` and gathers over sharded tables are
    refused; the sharding rules here (dist/sharding.py) pin layouts by
    constraint and let XLA propagate the rest, which is the Auto model.
    Install the mesh with ``jax.set_mesh(mesh)``; code inside jit reads it
    back through ``jax.sharding.get_abstract_mesh()``. ``devices`` defaults
    to ``jax.devices()``; a described (unattached) topology's devices give
    a mesh to compile against.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# Hardware constants for the roofline model (TPU v5e).
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~per chip, ring neighbor)
ICI_LAT = 1e-6                  # s fixed per-message latency on a link (the
                                # switch model's t_lat; charged once per wire
                                # message, so per-leaf gradient messaging
                                # pays it L times, the fused tier once)
VMEM_BYTES = 16 * 1024 * 1024
HBM_BYTES = 16 * 1024**3        # 16 GB per v5e chip
