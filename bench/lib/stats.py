"""Order statistics used by the drivers and by the spread checks."""
from __future__ import annotations

import math
import statistics


def nearest_rank(values, q: float) -> float:
    """The q-th percentile (0 < q <= 100) by nearest rank: the smallest
    value with at least q% of the sample at or below it. Works with
    ``math.inf`` entries (misses), which interpolation would not."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with Python's ``statistics.quantiles(values, n=4)``."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
