"""Trace reduction on synthetic events."""
import pytest

from bench.lib import reduce
from bench.lib.trace import DeviceTrace, TraceData, scopes_from_hlo


def test_merge_and_busy_union():
    iv = [(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)]
    assert reduce.merge(iv) == [(0, 20), (30, 45)]
    assert reduce.busy(iv, 0, 100) == 35
    assert reduce.busy(iv, 10, 35) == 15
    assert reduce.idle_share(iv, 0, 100) == pytest.approx(0.65)


def test_exposed_collective_time():
    compute = [(0, 10), (15, 30)]
    coll = [(5, 20), (28, 40)]
    # 10-15 and 30-40 run with no compute
    assert reduce.exposed(coll, compute) == 15
    assert reduce.exposed(coll, []) == 27
    assert reduce.exposed([(2, 8)], compute) == 0


def test_gaps_are_named_by_the_host_span_that_covers_them():
    ops = [(0, 10), (20, 30)]
    g = reduce.gaps(ops, 0, 50)
    assert g == [(10, 20), (30, 50)]
    spans = [("bench.put", 9, 14), ("bench.wait", 14, 21),
             ("bench.dispatch", 30, 35)]
    named = reduce.name_gaps(g, spans)
    assert named[0] == ("bench.dispatch", pytest.approx(20e-9))
    assert named[1] == ("bench.wait", pytest.approx(10e-9))
    assert reduce.name_gaps([(100, 110)], spans) == [
        ("no host span", pytest.approx(10e-9))]


def test_top_sums_by_name():
    ev = [("a", 0, 5), ("b", 5, 20), ("a", 20, 30)]
    assert reduce.top(ev, 1) == [("a", pytest.approx(15e-9))]


def _trace():
    ops = [("fusion.1", 0, 40), ("all-reduce.2", 40, 60),
           ("fusion.3", 50, 70), ("custom-call.4", 70, 80)]
    modules = [("jit_train_step(7)", 0, 80), ("jit_train_step(7)", 90, 170)]
    dev = DeviceTrace(ops, modules, (0, 130))
    return TraceData([dev], [("bench.trace_window", 0, 130),
                             ("bench.put", 80, 95)], (0, 130),
                     {"custom-call.4": "jit(train_step)/jit(_qdq_flat_impl)/"
                                       "pallas_call"})


def test_device_trace_counts_steps_by_share_inside_the_window():
    dev = _trace().devices[0]
    assert dev.module_count("train_step") == pytest.approx(1.5)
    # a run under way when the trace began is recorded cut short: it
    # counts by the length of a whole run, not by its own
    cut = DeviceTrace([], [("jit_train_step", 0, 30), ("jit_train_step",
                                                        30, 110),
                           ("jit_train_step", 110, 190)], (0, 150))
    assert cut.module_count("train_step") == pytest.approx(150 / 80)
    assert dev.module_seconds("train_step") == pytest.approx(120e-9)
    coll = dev.intervals(dev.is_collective)
    rest = dev.intervals(lambda n: not dev.is_collective(n))
    assert dev.exposed_seconds(coll, rest) == pytest.approx(10e-9)


def test_trace_data_idle_breakdown_and_scopes():
    tr = _trace()
    assert tr.window_s() == pytest.approx(130e-9)
    assert tr.busy_s() == pytest.approx(80e-9)
    assert tr.idle_share() == pytest.approx(50 / 130)
    assert "_qdq_flat_impl" in tr.scope("custom-call.4")
    b = tr.breakdown()
    assert b["device_ops"][0][0].startswith("fusion.1")
    # one gap, 80-130, which the host spent putting a batch
    assert b["idle_gaps"] == [["bench.put", pytest.approx(50e-9)]]


def test_scopes_from_compiled_hlo_text():
    hlo = ('  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
           'metadata={op_name="jit(train_step)/jit(_qdq_flat_impl)/sub" '
           'source_file="x.py"}\n'
           '  ROOT %tuple.3 = (f32[8]{0}) tuple(%fusion.12)\n')
    assert scopes_from_hlo(hlo) == {
        "fusion.12": "jit(train_step)/jit(_qdq_flat_impl)/sub"}
