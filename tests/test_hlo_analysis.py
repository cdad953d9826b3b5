"""The trip-count-aware HLO analyzer: unit fixtures + scan==unroll parity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis as H
from repro.launch import mesh as mesh_lib

SYNTH = """
HloModule test

%body.1 (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %p = (s32[], f32[8,16]) parameter(0)
  %w = f32[16,16]{1,0} constant(0)
  %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
  %dot.1 = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %all-reduce.1 = f32[8,16]{1,0} all-reduce(%dot.1), to_apply=%add.1
}

%cond.1 (p2: (s32[], f32[8,16])) -> pred[] {
  %p2 = (s32[], f32[8,16]) parameter(0)
  %c = s32[] constant(12)
  %i = s32[] get-tuple-element(%p2), index=0
  %cmp = pred[] compare(%i, %c), direction=LT
}

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  %s = f32[] add(%a, %b)
}

ENTRY %main (q: f32[8,16]) -> f32[8,16] {
  %q = f32[8,16]{1,0} parameter(0)
  %t = (s32[], f32[8,16]) tuple(s32[] constant(0), %q)
  %while.1 = (s32[], f32[8,16]) while(%t), condition=%cond.1, body=%body.1
  %w2 = f32[16,32]{1,0} constant(0)
  %dot.2 = f32[8,32]{1,0} dot(%q, %w2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %all-gather.7 = f32[64,32]{1,0} all-gather(%dot.2), dimensions={0}
}
"""


def test_synthetic_module_trips_and_costs():
    costs = H.analyze_hlo(SYNTH)
    # loop dot: 2*8*16*16 = 4096 flops x 12 trips; outer dot 2*8*32*16=8192
    assert costs.dot_flops == pytest.approx(4096 * 12 + 8192)
    # all-reduce 8*16*4 bytes x 12 + all-gather 64*32*4
    assert costs.collective_bytes == pytest.approx(8 * 16 * 4 * 12
                                                   + 64 * 32 * 4)
    assert costs.loops[0]["trips"] == 12


def test_scan_vs_unroll_parity_on_device():
    """The analyzer's core guarantee: scanned and unrolled versions of the
    same model report the same totals."""
    from repro import configs
    from repro.data.pipeline import make_batch_shapes
    from repro.dist import sharding
    from repro.models.common import InputShape
    from repro.optim import make_optimizer
    from repro.train import steps
    from repro.launch.dryrun import _state_shardings

    cfg = configs.get_config("qwen1.5-0.5b").reduced(n_layers=3)
    mesh = mesh_lib.make_mesh((1, 1), ("data", "model"))
    opt = make_optimizer("adamw", 1e-3)
    batch = make_batch_shapes(cfg, InputShape("t", 64, 4, "train"),
                              dtype=jnp.float32)

    def compile_one(scan):
        scfg = steps.TrainStepConfig(remat=False, scan_layers=scan)
        state = steps.abstract_train_state(cfg, opt, step_cfg=scfg)
        fn = steps.make_train_step(cfg, opt, scfg)
        with jax.set_mesh(mesh):
            j = jax.jit(fn, in_shardings=(
                _state_shardings(state, mesh),
                sharding.batch_shardings(batch, mesh)))
            return j.lower(state, batch).compile()

    cs = H.analyze_hlo(compile_one(True).as_text())
    cu = H.analyze_hlo(compile_one(False).as_text())
    assert cs.dot_flops == pytest.approx(cu.dot_flops, rel=0.02)
    assert any(l["trips"] == 3 for l in cs.loops)


def test_dot_flops_parser_handles_batch_dims():
    line = ("%dot.3 = f32[4,128,64]{2,1,0} dot(%a, %b), "
            "lhs_batch_dims={0}, rhs_batch_dims={0}, "
            "lhs_contracting_dims={2}, rhs_contracting_dims={1}")
    symbols = {"a": "f32[4,128,256]", "b": "f32[4,256,64]"}
    f = H._dot_flops_line(line, symbols)
    assert f == 2 * 4 * 128 * 64 * 256
