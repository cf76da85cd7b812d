"""Arithmetic every cell shares: percentiles with failures counted as
misses, and the spread the bounds are set from."""

from __future__ import annotations

import math
import statistics


def percentile(values: list[float], q: float) -> float:
    """``q`` in [0, 100], linear interpolation between closest ranks
    (NumPy's default). ``inf`` entries sort last and propagate."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    if xs[lo] == xs[hi] or pos == lo:
        return xs[lo]
    if math.isinf(xs[hi]):
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def latency_percentile(ok_latencies: list[float], n_failed: int,
                       q: float) -> float | None:
    """A request that failed or never finished misses every latency: it
    enters the tail as ``inf``. ``None`` when the percentile itself lands
    on a miss (there is then no number to report)."""
    vals = list(ok_latencies) + [math.inf] * n_failed
    if not vals:
        return None
    p = percentile(vals, q)
    return None if math.isinf(p) else p


def spread(values: list[float]) -> float:
    """Distance between the first and third quartile over the median,
    the quartiles as ``statistics.quantiles(values, n=4)`` gives them:
    the number a bound is five times of."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
