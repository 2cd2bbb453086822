"""Percentiles over all samples (no chunking, no medians of medians)."""
from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float | None:
    """The ``q``-th percentile (0-100) of all ``values``, linearly
    interpolated between closest ranks (numpy's default). ``None`` for an
    empty sample."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def spread(values) -> float:
    """Interquartile distance as a share of the median, with the quartiles
    of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
