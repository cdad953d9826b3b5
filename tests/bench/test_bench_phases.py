"""The train step's phase readers on synthetic traces: each operation goes
to one class by its scope path (collectives first, then the codec, then
the phase scopes), and each reader gives device time per step."""
import json

import pytest

from bench.lib import phases
from bench.lib.runner import Reading
from bench.lib.spec import Cell
from bench.lib.trace import DeviceTrace, TraceData

STEP = "jit(train_step)"
SCOPES = {
    "fusion.1": f"{STEP}/jvp(train.forward)/dot_general",
    "fusion.2": f"{STEP}/transpose(jvp(train.forward))/dot_general",
    # the gradient all-reduce carries the backward's scope
    "all-reduce.3": f"{STEP}/transpose(jvp(train.forward))/add_any",
    "fusion.4": f"{STEP}/train.clip/mul",
    "custom-call.5": f"{STEP}/train.codec/shard_map/jit(_qdq_flat_impl)/"
                     "quant.qdq_flat/pallas_call",
    "sub.6": f"{STEP}/train.error_feedback/sub",
    "fusion.7": f"{STEP}/train.optimizer/add",
}
# one step's operations, ns from the step's start; copy.8 has no scope
STEP_OPS = [("fusion.1", 0, 10), ("fusion.2", 10, 40),
            ("all-reduce.3", 30, 50), ("fusion.4", 50, 60),
            ("custom-call.5", 60, 75), ("sub.6", 75, 80),
            ("fusion.7", 80, 95), ("copy.8", 95, 97)]
HLO = """HloModule jit_train_step
ENTRY %main (p: f32[1000]) -> f32[1000] {
  %p = f32[1000]{0} parameter(0)
  ROOT %all-reduce.3 = f32[1000]{0} all-reduce(%p), to_apply=%add
}
"""
CELLS = ["train.repro-100m.rq8ef", "train.repro-100m.dp4.rq8ef"]
METRICS = ["forward_ms_per_step.train", "backward_ms_per_step.train",
           "grad_prep_ms_per_step.train", "optimizer_ms_per_step.train",
           "collective_gb_per_s.train"]


def _device(n_steps=2, stretch=1):
    """A chip that ran ``n_steps`` steps, each op ``stretch`` times as
    long as in ``STEP_OPS``."""
    period = 100 * stretch
    ops = [(n, period * k + s * stretch, period * k + e * stretch)
           for k in range(n_steps) for n, s, e in STEP_OPS]
    modules = [("jit_train_step(3)", period * k, period * (k + 1))
               for k in range(n_steps)]
    return DeviceTrace(ops, modules, (0, period * n_steps))


def _trace(devices=None, scopes=SCOPES):
    devices = devices or [_device()]
    return TraceData(devices, [], devices[0].window, dict(scopes))


def _reading(trace, hlo=HLO):
    cell = Cell(CELLS[1])
    return Reading(trace, {"hlo_text": hlo}, {}, cell.config, cell.traffic,
                   cell.family())


@pytest.mark.parametrize("name,cls", [
    ("fusion.1", "forward"), ("fusion.2", "backward"),
    ("all-reduce.3", "exchange"), ("fusion.4", "grad_prep"),
    ("custom-call.5", "codec"), ("sub.6", "grad_prep"),
    ("fusion.7", "optimizer"), ("copy.8", None)])
def test_each_operation_goes_to_one_class(name, cls):
    assert phases.classify(name, SCOPES.get(name, name)) == cls


def test_a_collective_is_found_by_its_opcode_and_before_any_scope():
    scope = f"{STEP}/transpose(jvp(train.forward))/psum"
    assert phases.classify("ar.9", scope) == "backward"
    assert phases.classify("ar.9", scope, "all-reduce-start") == "exchange"
    assert phases.Hlo(HLO).opcode == {"p": "parameter",
                                      "all-reduce.3": "all-reduce"}
    # the codec's kernels are never charged to a phase, even under one
    assert phases.classify(
        "fusion.10", f"{STEP}/transpose(jvp(train.forward))/"
                     "jit(_qdq_flat_impl)/add") == "codec"


@pytest.mark.parametrize("cls,ns", [("forward", 10), ("backward", 30),
                                    ("grad_prep", 15), ("optimizer", 15),
                                    ("codec", 15), ("exchange", 20)])
def test_seconds_per_step_is_the_union_over_the_steps(cls, ns):
    # two steps in the window: per step, not per window
    assert phases.seconds_per_step(_trace(), HLO, cls) == pytest.approx(
        ns * 1e-9)


def test_readers_give_ms_per_step_averaged_over_the_chips():
    readers = Cell(CELLS[1]).metric_readers()
    # the second chip ran every operation twice as long
    r = _reading(_trace([_device(), _device(stretch=2)]))
    assert readers["forward_ms_per_step.train"].read(r) == pytest.approx(
        15e-6)
    # backward: 10-40 (and 20-80), the all-reduce left out
    assert readers["backward_ms_per_step.train"].read(r) == pytest.approx(
        45e-6)
    assert readers["grad_prep_ms_per_step.train"].read(
        r) == pytest.approx(22.5e-6)
    assert readers["optimizer_ms_per_step.train"].read(
        r) == pytest.approx(22.5e-6)


def test_collective_bandwidth_is_message_bytes_over_exchange_time():
    read = Cell(CELLS[1]).metric_readers()["collective_gb_per_s.train"].read
    # 4000 B per step over 20 ns per step
    assert read(_reading(_trace())) == pytest.approx(4000 / 20e-9 / 1e9)
    assert read(_reading(_trace(), hlo="")) is None


@pytest.mark.parametrize("metric", METRICS)
def test_reader_gives_none_without_steps_or_scopes(metric):
    read = Cell(CELLS[1]).metric_readers()[metric].read
    no_steps = DeviceTrace(_device().ops, [], (0, 200))
    assert read(_reading(_trace([no_steps]))) is None
    if metric != "collective_gb_per_s.train":
        # a program whose step names no phase, as before the scopes
        assert read(_reading(_trace(scopes={}))) is None


def test_split_names_the_rest_and_the_share_covered():
    row, = phases.split(_trace(), HLO)
    assert row["steps"] == pytest.approx(2)
    assert row["busy"] == pytest.approx(97e-6)
    assert row["rest"] == pytest.approx(2e-6)
    assert row["covered"] == pytest.approx(95 / 97)
    assert phases.split(_trace([DeviceTrace([], [], (0, 10))]), HLO) == [None]


@pytest.mark.parametrize("cell", CELLS)
def test_phase_metrics_are_read_in_both_cells(cell):
    names = {m["name"] for m in Cell(cell).per_layer}
    want = set(METRICS[:4]) | ({METRICS[4]} if "dp4" in cell else set())
    assert want <= names


# an unscoped prefetch (async slice) of a parameter feeding the forward,
# an unscoped copy of the optimizer's output, and the codec key's fold_in,
# whose scope names no phase
ADDED_HLO = """HloModule jit_train_step

%async_computation.1 (param_0.1: f32[8]) -> f32[4] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %slice.1 = f32[4]{0} slice(%param_0.1), slice={[0:4]}
}

ENTRY %main.9 (p: f32[8]) -> (f32[4], f32[8]) {
  %p = f32[8]{0} parameter(0), metadata={op_name="state['params']"}
  %slice-start.1 = ((f32[8]{0}), f32[4]{0}, s32[]) async-start(%p), calls=%async_computation.1
  %slice-done.1 = f32[4]{0} async-done(%slice-start.1)
  %fusion.1 = f32[4]{0} fusion(%slice-done.1), kind=kLoop, calls=%fc.1, metadata={op_name="jit(train_step)/jvp(train.forward)/mul"}
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc.7, metadata={op_name="jit(train_step)/train.optimizer/add"}
  %copy.8 = f32[8]{0} copy(%fusion.7)
  %xor.2 = u32[2]{0} xor(%p), metadata={op_name="jit(train_step)/train.codec/jit(_threefry_fold_in)/xor"}
  %copy.3 = u32[2]{0} copy(%xor.2)
  ROOT %tuple.9 = (f32[4]{0}, f32[8]{0}) tuple(%fusion.1, %copy.8)
}
"""


@pytest.mark.parametrize("name,cls", [
    ("slice-start.1", "forward"), ("slice-done.1", "forward"),
    ("slice.1", "forward"), ("copy.8", "optimizer"), ("xor.2", None),
    ("copy.3", None), ("p", None)])
def test_an_op_the_compiler_added_takes_the_class_of_its_user(name, cls):
    from bench.lib.trace import scopes_from_hlo
    classes = phases.hlo_classes(scopes_from_hlo(ADDED_HLO), ADDED_HLO)
    assert classes[name] == cls


def test_phase_tool_prints_the_result_and_each_chips_split(
        cpu_run, monkeypatch, capsys):
    import jax
    from bench.lib import runner, spec, trace as trace_lib
    from conftest import tiny_cell
    monkeypatch.setattr(runner, "accelerators",
                        lambda chips: jax.devices()[:chips])
    real_cell = spec.Cell
    monkeypatch.setattr(spec, "Cell", lambda name: tiny_cell(real_cell(name)))

    def load(trace_dir, *, n_devices, hlo_text=""):
        # the CPU's profile holds no TPU plane: a chip's trace instead
        return _trace()
    monkeypatch.setattr(trace_lib, "load", load)
    assert phases.main(["--workload", CELLS[0], "--seed", "12345",
                        "--seconds", "1.5"]) == 0
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"]
    assert res["metrics"]["forward_ms_per_step.train"]["value"] == \
        pytest.approx(10e-6)
    assert "phase split, chip 0" in out.err
    assert trace_lib.load is load       # the harness's loader is put back
