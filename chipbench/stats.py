"""Percentile and window arithmetic shared by the metric readers."""
from __future__ import annotations

import math
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    the closest ranks of the sorted sample (numpy's default method)."""
    if not values:
        raise ValueError("percentile of an empty sample")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def supported_percentile(values: Sequence[float], q: float,
                         beyond: int = 10) -> Optional[float]:
    """The ``q``-th percentile, or None when fewer than ``beyond`` samples
    lie above it (the sample cannot support that tail)."""
    if len(values) * (100.0 - q) / 100.0 < beyond - 1e-9:
        return None
    return percentile(values, q)


def rate(count: int, window_s: float) -> float:
    """Events per second over a window."""
    if window_s <= 0:
        raise ValueError("empty window")
    return count / window_s
