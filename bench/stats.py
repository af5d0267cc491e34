"""Rate, tail and spread arithmetic shared by every cell.

Tails are taken over all samples of the window, never as a median of
per-chunk percentiles, and rates over all the work and all the time of
the window.
"""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of all ``values``, linear
    interpolation between closest ranks (numpy's default)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return statistics.median(float(v) for v in values)


def rate(amount: float, window_s: float) -> float:
    """Work per second over the whole window."""
    if window_s <= 0:
        raise ValueError(f"window of {window_s} s")
    return amount / window_s


def spread(values) -> float:
    """Interquartile distance as a share of the median (Python's
    ``statistics.quantiles(values, n=4)``)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def intertoken_gaps(times_by_request: dict) -> list:
    """Every gap between consecutive tokens of one request, over all
    requests: ``{uid: [t0, t1, ...]}`` (seconds, in arrival order)."""
    gaps = []
    for ts in times_by_request.values():
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    return gaps
