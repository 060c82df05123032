"""Percentiles and spreads over raw samples (the benchmark's own copy)."""
from __future__ import annotations

import statistics
from typing import Sequence


def percentile(xs: Sequence[float], q: float) -> float:
    """Linearly interpolated percentile of raw samples (numpy's default
    method), ``q`` in [0, 100]."""
    s = sorted(float(x) for x in xs)
    if not s:
        raise ValueError("percentile of an empty sample")
    if not 0 <= q <= 100:
        raise ValueError(f"q must lie in [0, 100]; got {q}")
    pos = q / 100.0 * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return min(s[lo] + (s[hi] - s[lo]) * (pos - lo), s[hi])


def spread(xs: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, by ``statistics.quantiles(xs, n=4)``."""
    q1, med, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / med
