"""Metric arithmetic: percentiles, the tail rule, spreads."""

from __future__ import annotations

import numpy as np


def tail_percentile(n: int) -> float:
    """The highest percentile with at least ten samples beyond it,
    from the usual ladder; 50 when the sample is too small for any."""
    for p in (99.9, 99.0, 95.0, 90.0):
        if round(n * (100.0 - p) / 100.0, 6) >= 10.0:
            return p
    return 50.0


def percentile(samples, p: float) -> float:
    """Nearest-rank percentile (no interpolation: a reported latency is
    one that a request had)."""
    a = np.sort(np.asarray(samples, dtype=np.float64))
    if a.size == 0:
        raise ValueError("no samples")
    rank = int(np.ceil(p / 100.0 * a.size))
    return float(a[min(max(rank, 1), a.size) - 1])


def spread(values) -> float:
    """Distance between the quartiles over the median — the driver's
    measure of run-to-run noise."""
    a = np.asarray(values, dtype=np.float64)
    q1, med, q3 = np.percentile(a, [25, 50, 75])
    return float((q3 - q1) / med)
