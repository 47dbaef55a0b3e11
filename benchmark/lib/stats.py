"""Percentile and spread arithmetic (end-to-end metrics are built on it)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q`` of
    the sample at or below it (``bench_serve._pct``'s arithmetic).  An
    empty sample has no percentile."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    return vals[min(len(vals) - 1, max(0, math.ceil(q * len(vals)) - 1))]


def iqr_share(values) -> float:
    """Distance between the first and third quartile over the median, with
    the quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
