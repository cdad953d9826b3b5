"""Sharding-rule unit tests + a real (1x1 mesh) lower/compile integration."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.data.pipeline import make_batch_shapes
from repro.dist import sharding
from repro.launch import mesh as mesh_lib
from repro.models.common import InputShape
from repro.optim import make_optimizer
from repro.train import steps


class FakeKey:
    def __init__(self, key):
        self.key = key


def _mesh(shape=(1, 1)):
    # single real device: a 1x1 mesh still exercises the full spec logic
    return mesh_lib.make_mesh(shape, ("data", "model")[:len(shape)])


def _spec(pathnames, shape, mesh):
    path = tuple(FakeKey(n) for n in pathnames)
    return sharding.param_spec(path, shape, mesh)


def test_column_parallel_rule():
    mesh = _mesh()
    spec = _spec(("layers", "0", "mixer", "q", "w"), (1024, 2048), mesh)
    assert spec == P("data", "model")


def test_row_parallel_rule():
    mesh = _mesh()
    spec = _spec(("layers", "0", "mixer", "o", "w"), (2048, 1024), mesh)
    assert spec == P("model", "data")


def test_rwkv_channel_mix_v_is_row_parallel():
    mesh = _mesh()
    spec = _spec(("layers", "0", "ffn", "v", "w"), (2048, 1024), mesh)
    assert spec == P("model", "data")
    # attention 'v' stays column-parallel
    spec2 = _spec(("layers", "0", "mixer", "v", "w"), (1024, 128), mesh)
    assert spec2 == P("data", "model")


def test_maybe_divisibility():
    mesh = _mesh()
    assert sharding._maybe("model", 7, mesh) == "model"  # 7 % 1 == 0
    # a 4x4 mesh described without devices
    M = AbstractMesh((4, 4), ("data", "model"))
    assert sharding._maybe("model", 7, M) is None
    assert sharding._maybe("model", 8, M) == "model"
    assert sharding._maybe(("data",), 8, M) == ("data",)


def test_scan_stacked_param_replicates_layer_dim():
    M = AbstractMesh((4, 4), ("data", "model"))
    spec = _spec(("scan_blocks", "0", "mixer", "q", "w"), (24, 1024, 2048), M)
    assert spec == P(None, ("data",), "model")


def test_moe_bank_rules():
    M = AbstractMesh((4, 4), ("data", "model"))
    assert _spec(("layers", "0", "ffn", "w_gate"), (8, 4096, 32768), M) == \
        P(None, ("data",), "model")
    assert _spec(("layers", "0", "ffn", "w_down"), (8, 32768, 4096), M) == \
        P(None, "model", ("data",))


def test_cache_spec_gqa_head_dim_fallback():
    M = AbstractMesh((16, 16), ("data", "model"))
    path = tuple(FakeKey(n) for n in ("layers", "0", "k"))
    # kv_heads=8 not divisible by 16 -> shard head_dim 128 instead
    spec = sharding.cache_spec(path, (128, 32768, 8, 128), M)
    assert spec == P("data", None, None, "model")
    # kv_heads=16 divisible -> shard heads
    spec2 = sharding.cache_spec(path, (128, 32768, 16, 64), M)
    assert spec2 == P("data", None, "model", None)


def test_batch_spec():
    M = AbstractMesh((16, 16), ("data", "model"))
    assert sharding.batch_spec((256, 4096), M) == P(("data",), None)
    assert sharding.batch_spec((1, 524288), M) == P(None, None)


def test_lower_compile_reduced_arch_on_host_mesh():
    """Integration: the dryrun wiring lowers + compiles on the real device
    (1x1 mesh), for a train step and a decode step."""
    from repro.models import transformer_scan
    cfg = configs.get_config("qwen1.5-0.5b").reduced()
    mesh = _mesh()
    opt = make_optimizer("adamw", 1e-3)
    scfg = steps.TrainStepConfig(remat=True, scan_layers=True)
    state = steps.abstract_train_state(cfg, opt, step_cfg=scfg)
    batch = make_batch_shapes(cfg, InputShape("t", 64, 4, "train"),
                              dtype=jnp.float32)
    from repro.launch.dryrun import _state_shardings
    with jax.set_mesh(mesh):
        fn = steps.make_train_step(cfg, opt, scfg)
        j = jax.jit(fn, in_shardings=(_state_shardings(state, mesh),
                                      sharding.batch_shardings(batch, mesh)))
        compiled = j.lower(state, batch).compile()
    assert compiled.cost_analysis() is not None

    params = jax.eval_shape(
        lambda k: transformer_scan.init(cfg, k, dtype=jnp.float32),
        jax.random.PRNGKey(0))
    dstate = jax.eval_shape(
        lambda p: transformer_scan.init_decode_state(p, cfg, 4, 64),
        params)
    dbatch = {"tokens": jax.ShapeDtypeStruct((4, 1), jnp.int32)}
    with jax.set_mesh(mesh):
        sfn = steps.make_serve_step(cfg, scan_layers=True)
        j2 = jax.jit(sfn, in_shardings=(
            sharding.params_shardings(params, mesh),
            sharding.cache_shardings(dstate, mesh),
            sharding.batch_shardings(dbatch, mesh)))
        compiled2 = j2.lower(params, dstate, dbatch).compile()
    assert compiled2.cost_analysis() is not None
