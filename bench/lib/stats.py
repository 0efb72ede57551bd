"""Order statistics: the percentiles of the metric readers, and the
spreads that the bounds of BENCHMARK.json are set from (PERF.md §2)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0 <= q <= 100) by linear interpolation
    between closest ranks (numpy's default method).  Raises on an empty
    sample: a tail of nothing is no number."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0.0 <= q <= 100.0:
        raise ValueError(f"q={q} outside [0, 100]")
    h = (len(xs) - 1) * q / 100.0
    lo = math.floor(h)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (h - lo) * (xs[hi] - xs[lo])


def beyond(n: int, q: float) -> int:
    """Samples strictly beyond the q-th percentile of n samples."""
    return n - 1 - math.floor((n - 1) * q / 100.0)


def spread(values) -> float:
    """Distance between the first and third quartiles as a share of the
    median, with ``statistics.quantiles(values, n=4)``'s quartiles."""
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def spread_trimmed(values) -> float:
    """``spread`` with the value farthest from the median left out."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return spread([v for i, v in enumerate(values) if i != far])
