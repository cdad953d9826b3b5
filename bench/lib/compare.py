"""The comparisons that decide ``correct``: each number beside its limit."""
from __future__ import annotations

import statistics


def rel_gap(a: float, b: float) -> float:
    """|a - b| / |b|."""
    return abs(a - b) / abs(b)


def worst_leaf_gap(prog: dict, ref: dict, *, keep=None) -> float:
    """Worst gap between the program's and the reference's norm of a
    leaf, as a share of the larger of that leaf's reference norm and the
    median leaf's. ``keep`` names the leaves compared (default: all)."""
    names = sorted(ref) if keep is None else sorted(keep)
    if sorted(prog) != sorted(ref):
        raise ValueError("the program's leaves are not the reference's")
    med = statistics.median(ref[k] for k in names)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in names)


def moving_leaves(grad_norms: dict, share: float = 1e-3) -> list:
    """Leaves whose reference gradient norm is at least ``share`` of the
    median leaf's. The others move under Adam by round-off alone."""
    med = statistics.median(grad_norms.values())
    return [k for k, g in grad_norms.items() if g >= share * med]


def judge(numbers: dict, limits: dict) -> list:
    """``[(name, value, limit)]`` for every limit; a number the run did
    not produce reads as infinite."""
    return [(k, float(numbers.get(k, float("inf"))), float(lim))
            for k, lim in sorted(limits.items())]


def passed(checks: list) -> bool:
    return all(v <= lim for _, v, lim in checks)
