"""The main path's Pallas kernels compile for a TPU v5e, at real widths.

Each test compiles for a described (unattached) v5e chip: nothing runs, so
this checks what interpret mode cannot — block shapes the TPU lowering
accepts and tiles that fit VMEM. The topology is described in a fixture,
only once a test of this file runs, and the tests skip where it cannot be
described.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attn import ops as flash_ops
from repro.kernels.quant import ops as quant_ops
from repro.kernels.wkv6 import ops as wkv6_ops

# the repro-100m gradient: FlatLayout.from_tree(params).total
REPRO_100M_GRAD = 128_994_048


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off (entries written for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_for_tpu(monkeypatch):
    """Kernels lower for the chip (interpret off); returns a compile fn."""
    for mod in (quant_ops, flash_ops, wkv6_ops):
        monkeypatch.setattr(mod, "_interpret", lambda: False)

    def compile_(fn, *args):
        return jax.jit(fn).lower(*args).compile().as_text()

    return compile_


def _shape(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _instructions(hlo: str):
    """(opcode, dtype, elements, op_name) of every HLO instruction, fused
    computations' included (a Mosaic kernel's body is not HLO)."""
    pat = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = \(?(\w+)\[([\d,]*)\]"
                     r"\S* ([\w\-]+)\(")
    for line in hlo.splitlines():
        m = pat.match(line)
        if m:
            dtype, dims, opcode = m.groups()
            name = re.search(r'op_name="([^"]*)"', line)
            yield (opcode, dtype, math.prod(int(d) for d in dims.split(",")
                                            if d), name.group(1) if name
                   else "")


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_qdq_flat_compiles_at_repro_100m_gradient(one_chip, compiled_for_tpu,
                                                  bits):
    """The compiled codec is its two kernels over the unpadded buffer: no
    uniforms drawn outside them (the only threefry work left is the
    per-bucket key fold-ins) and no pad, update, copy or concatenation
    that writes a buffer of the gradient's size."""
    flat = _shape(one_chip, (REPRO_100M_GRAD,), jnp.float32)
    key = _shape(one_chip, (2,), jnp.uint32)
    hlo = compiled_for_tpu(
        lambda f, k: quant_ops.qdq_flat(f, k, bits=bits, backend="pallas"),
        flat, key)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    _, _, nb, _, _ = quant_ops.flat_geometry(REPRO_100M_GRAD, bits=bits)
    ops = list(_instructions(hlo))
    assert not [o for o in ops if o[0].startswith("rng")]
    draws = [o for o in ops if re.search(r"threefry|uniform|random_bits",
                                         o[3])]
    assert draws and all(o[2] <= 2 * nb and "uniform" not in o[3]
                         for o in draws), draws
    assert not [o for o in ops
                if o[0] in ("pad", "dynamic-update-slice", "copy",
                            "concatenate")
                and o[1] == "f32" and o[2] >= REPRO_100M_GRAD]


def test_ring_hop_compiles_at_repro_100m_partition(one_chip,
                                                   compiled_for_tpu):
    part, nb, rows = quant_ops.partition_geometry(REPRO_100M_GRAD, 4, bits=8)
    hlo = compiled_for_tpu(
        lambda p, q, x, k: quant_ops.decode_add_encode_flat(
            p, q, x, k, bits=8, backend="pallas"),
        _shape(one_chip, (rows, quant_ops.LANES), jnp.uint8),
        _shape(one_chip, (nb, 2), jnp.float32),
        _shape(one_chip, (part,), jnp.float32),
        _shape(one_chip, (2,), jnp.uint32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles_at_repro_100m_heads(one_chip,
                                                      compiled_for_tpu,
                                                      dtype):
    cfg = configs.get_config("repro-100m")
    q = _shape(one_chip, (1, 2048, cfg.n_heads, cfg.head_dim), dtype)
    kv = _shape(one_chip, (1, 2048, cfg.n_kv_heads, cfg.head_dim), dtype)
    hlo = compiled_for_tpu(
        lambda a, b, c: flash_ops.flash_attention(a, b, c, causal=True),
        q, kv, kv)
    assert "tpu_custom_call" in hlo


def test_wkv6_compiles_at_rwkv6_3b_heads(one_chip, compiled_for_tpu):
    cfg = configs.get_config("rwkv6-3b")
    h, dk = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    x = _shape(one_chip, (1, 2048, h, dk), jnp.float32)
    hlo = compiled_for_tpu(
        lambda r, k, v, w, u: wkv6_ops.wkv6(r, k, v, w, u),
        x, x, x, x, _shape(one_chip, (h, dk), jnp.float32))
    assert "tpu_custom_call" in hlo
