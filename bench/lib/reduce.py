"""Reduction of device and host intervals to numbers.

Pure functions over (start, end) pairs in nanoseconds, so that they can be
checked on synthetic events: the union of device-busy intervals, the idle
share of a window, collective time during which no compute ran, and the
idle gaps named by what the host was doing in them.
"""
from __future__ import annotations


def merge(intervals) -> list:
    """Sorted, non-overlapping union of ``(start, end)`` pairs."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return float(sum(e - s for s, e in merge(intervals)))


def busy(intervals, lo, hi) -> float:
    """Time inside [lo, hi] in which at least one interval is open."""
    return length(clip(intervals, lo, hi))


def idle_share(intervals, lo, hi) -> float:
    """1 - busy / window, in [0, 1]."""
    if hi <= lo:
        raise ValueError("empty window")
    return 1.0 - busy(intervals, lo, hi) / (hi - lo)


def exposed(collectives, compute) -> float:
    """Length of the union of ``collectives`` not covered by ``compute``."""
    cover = merge(compute)
    total = 0.0
    for s, e in merge(collectives):
        seen = s
        for cs, ce in cover:
            if ce <= seen:
                continue
            if cs >= e:
                break
            if cs > seen:
                total += cs - seen
            seen = max(seen, ce)
            if seen >= e:
                break
        if seen < e:
            total += e - seen
    return total


def gaps(intervals, lo, hi) -> list:
    """Idle gaps ``(start, end)`` of the window [lo, hi]."""
    out, t = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def name_gaps(gap_list, spans, default: str = "no host span") -> list:
    """``[(name, seconds)]`` for each gap, longest first, where ``name``
    is the host span (name, start, end) overlapping the gap the most."""
    out = []
    for gs, ge in gap_list:
        best, best_ov = default, 0.0
        for name, ss, se in spans:
            ov = min(ge, se) - max(gs, ss)
            if ov > best_ov:
                best, best_ov = name, ov
        out.append((best, (ge - gs) * 1e-9))
    return sorted(out, key=lambda x: -x[1])


def top(named, k: int = 10) -> list:
    """``[(name, seconds)]`` summed by name over ``(name, start, end)``
    events, the ``k`` largest first."""
    acc: dict = {}
    for name, s, e in named:
        acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
    return sorted(acc.items(), key=lambda x: -x[1])[:k]


def clip_named(named, lo, hi) -> list:
    """``(name, start, end)`` events cut to the window [lo, hi]."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in named
            if min(e, hi) > max(s, lo)]
