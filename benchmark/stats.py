"""Percentiles and the spread rule, in one place.

`percentile` is the nearest-rank arithmetic of tools/loadgen.py's `_pct`
(copied: the yardstick must not move when the program's tools do).
"""

from __future__ import annotations

import statistics
from typing import List, Optional, Sequence


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of xs at q in [0, 1]; None for no data."""
    xs = sorted(xs)
    if not xs:
        return None
    k = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
    return xs[k]


def samples_beyond(xs: Sequence[float], q: float) -> int:
    """How many samples lie strictly beyond the q-th percentile's rank."""
    n = len(xs)
    if not n:
        return 0
    k = min(n - 1, max(0, int(round(q * (n - 1)))))
    return n - 1 - k


def median(xs: Sequence[float]) -> Optional[float]:
    xs = list(xs)
    return statistics.median(xs) if xs else None


def iqr_share(xs: List[float]) -> Optional[float]:
    """(Q3 - Q1) / median, as the driver's spread rule has it."""
    if len(xs) < 2:
        return None
    q = statistics.quantiles(xs, n=4)
    m = statistics.median(xs)
    return (q[2] - q[0]) / m if m else None
