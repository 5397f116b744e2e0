"""The arithmetic every metric shares: medians, geometric means, and the
spread the bounds are set from."""
from __future__ import annotations

import math
import statistics


def median(values) -> float:
    return float(statistics.median(values))


def geomean(values) -> float:
    """Geometric mean: every point weighs the same, so a 64 MiB point
    cannot hide a 4 MiB one.  Values must be above 0."""
    values = list(values)
    if not values or any(v <= 0 for v in values):
        raise ValueError(f"geometric mean needs values above 0: {values}")
    return math.exp(sum(math.log(v) for v in values) / len(values))


def quartiles(values) -> tuple:
    """(q1, q3) by the inclusive method, as the spread rule uses them."""
    q = statistics.quantiles(sorted(values), n=4, method="inclusive")
    return q[0], q[2]


def spread(values) -> float:
    """The distance between the quartiles over the median."""
    q1, q3 = quartiles(values)
    return (q3 - q1) / median(values)
