"""Metric arithmetic on the host clock.

A rate is taken over all the work and all the time of the window; a
tail is the tail of every request completed in it.  Nothing here takes
a median of chunks or drops a slow stretch."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile (q in (0, 100]) of every value."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return float(s[rank - 1])


def rate(count: int, window_s: float) -> float:
    """Completed work over the whole window's seconds."""
    if window_s <= 0:
        raise ValueError("empty window")
    return count / window_s
