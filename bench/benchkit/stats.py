"""Percentiles and spreads, computed the same way in every run.

A tail is the nearest-rank percentile over every request of the window:
the smallest sample with at least ``p`` percent of all samples at or
below it.  A request that failed, was refused or never resolved is a
sample of ``inf``: it misses every latency limit, so enough of them move
the tail to ``inf`` rather than vanish from it.
"""
from __future__ import annotations

import math
import statistics
from typing import Sequence


def percentile(samples: Sequence[float], p: float) -> float:
    """Nearest-rank ``p``-th percentile (0 < p <= 100) of ``samples``."""
    if not samples:
        raise ValueError("percentile of no samples")
    if not 0 < p <= 100:
        raise ValueError(f"p wants (0, 100], got {p}")
    ordered = sorted(samples)
    rank = max(1, math.ceil(p / 100.0 * len(ordered)))
    return float(ordered[rank - 1])


def spread(values: Sequence[float]) -> float:
    """Quartile spread as a share of the median: ``(q3 - q1) / median``,
    with the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
