"""Metric arithmetic shared by the readers: percentiles, means, spread.

``percentile`` is the linear-interpolation percentile of the sorted
sample (numpy's default), kept here so that no reader depends on the
program's own arithmetic.  ``spread`` is the distance between the first
and third quartile as ``statistics.quantiles(values, n=4)`` gives them,
as a share of the median: the rule the driver uses for a bound."""

from __future__ import annotations

import statistics
from typing import Iterable, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """q in [0, 100]; None for an empty sample."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def mean(values: Iterable[float]) -> Optional[float]:
    xs = list(values)
    return float(sum(xs) / len(xs)) if xs else None


def spread(values: Sequence[float]) -> Optional[float]:
    """(Q3 - Q1) / median of at least two values."""
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / abs(med) if med else None


def completion_share(done_times: Sequence[float], rate: float) -> float:
    """Share of the offered pace the system kept up with: the served
    rate over the offered one, the served rate being the least-squares
    slope of completion index against completion time over the middle
    of the run (first fifth and last twentieth trimmed).  A system that
    keeps up completes at the arrival rate and gives about 1; a
    saturated one gives its ceiling over the rate.  (bench.py's knee
    criterion, copied: at least 0.99 is "sustained".)"""
    done = sorted(done_times)
    n = len(done)
    ts = done[max(1, n // 5):n - max(1, n // 20)]
    if len(ts) < 3:
        return 1.0
    k = len(ts)
    mx, my = sum(ts) / k, (k - 1) / 2.0
    sxx = sum((t - mx) ** 2 for t in ts)
    sxy = sum((t - mx) * (i - my) for i, t in enumerate(ts))
    served = sxy / sxx if sxx > 0 else rate
    return min(1.0, served / rate)
