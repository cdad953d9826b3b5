"""The comparisons behind ``correct`` and the order statistics."""
import math

import pytest

from bench.lib import compare, stats
from bench.lib.runner import Tracer


def test_worst_leaf_gap_is_relative_to_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 2.0, "c": 3.0, "tiny": 1e-6}
    prog = {"a": 1.1, "b": 2.0, "c": 3.0, "tiny": 2e-6}
    # median of the reference norms is 1.5: a's gap 0.1 / 1.5, and the
    # all-but-zero leaf's gap 1e-6 / 1.5 instead of 100%
    assert compare.worst_leaf_gap(prog, ref) == pytest.approx(0.1 / 1.5)
    with pytest.raises(ValueError):
        compare.worst_leaf_gap({"a": 1.0}, ref)


def test_leaves_moved_by_round_off_alone_are_left_out():
    g = {"w": 1.0, "v": 0.5, "bias_under_softmax": 1e-9}
    assert sorted(compare.moving_leaves(g)) == ["v", "w"]


def test_a_state_left_unchanged_reads_one():
    ref = {"a": 0.3, "b": 0.4}
    assert compare.worst_leaf_gap({"a": 0.0, "b": 0.0}, ref) == 1.0


def test_judge_reads_a_missing_or_nan_number_as_a_failure():
    checks = compare.judge({"x": 0.5, "y": float("nan")},
                           {"x": 1.0, "y": 1.0, "z": 0.0})
    assert [c[0] for c in checks] == ["x", "y", "z"]
    assert checks[2][1] == math.inf
    assert not compare.passed(checks)
    assert compare.passed(compare.judge({"x": 0.5}, {"x": 1.0}))


def test_nearest_rank_and_spread():
    xs = list(range(1, 101))
    assert stats.nearest_rank(xs, 90) == 90
    # 10 misses in 100: the 95th percentile is a miss
    assert stats.nearest_rank(xs[:90] + [math.inf] * 10, 95) == math.inf
    assert stats.nearest_rank([3.0], 50) == 3.0
    assert stats.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    assert stats.spread([90, 95, 100, 105, 110]) == pytest.approx(0.15)


def test_a_tracer_that_is_off_never_starts():
    t = Tracer(False, 0.0, 1.0)
    t.tick(5.0)
    t.stop()
    assert not t.active and t.dir is None
