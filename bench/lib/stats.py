"""Latency statistics of one window.

Every attempted request has a latency: a request that never completed
carries the time it had waited when the window's grace closed, which
is beyond every limit the benchmark sets.  Percentiles take the
nearest-rank value (no interpolation), so a tail is always one
request's real latency.
"""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100)."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    k = max(math.ceil(q / 100.0 * len(xs)), 1)
    return float(xs[k - 1])


def goodput(latencies_ms: Sequence[float], completed: Sequence[bool],
            budget_ms: float, seconds: float) -> float:
    """Attempted requests that completed within ``budget_ms``, per
    second of window.  A failed request never counts."""
    ok = sum(1 for lat, done in zip(latencies_ms, completed)
             if done and lat <= budget_ms)
    return ok / seconds


def spread(values: Sequence[float]) -> float:
    """Interquartile range as a share of the median (the rule the
    bounds in BENCHMARK.json were set by)."""
    import statistics
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
