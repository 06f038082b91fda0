"""Percentiles, rates and spreads as the benchmark reports them.

A tail percentile is reported only when the sample supports it: at
least ``MIN_BEYOND`` samples must lie beyond it, so a 95th percentile
needs 200 samples. A missing value (a failed request) sorts as +inf:
it counts as missing every latency limit.
"""

from __future__ import annotations

import math
import statistics

MIN_BEYOND = 10


class TooFewSamples(ValueError):
    """The sample cannot support the requested percentile."""


def min_samples(q: float) -> int:
    """Smallest sample with ``MIN_BEYOND`` values beyond the q-th
    percentile (q in percent); the median needs only one value."""
    if q <= 50:
        return 1
    return math.ceil(MIN_BEYOND / (1 - q / 100) - 1e-9)


def percentile(values, q: float) -> float:
    """Nearest-rank q-th percentile (q in percent) of ``values``.

    Nearest rank returns a value that occurred, never an interpolation,
    so a tail reads the latency of a real request. ``None`` entries are
    missing values and sort above every number.
    """
    xs = sorted(math.inf if v is None else float(v) for v in values)
    if len(xs) < min_samples(q):
        raise TooFewSamples(
            f"p{q:g} needs {min_samples(q)} samples for {MIN_BEYOND} beyond "
            f"it; got {len(xs)}"
        )
    rank = max(1, math.ceil(q / 100 * len(xs)))
    return xs[rank - 1]


def rate(count: float, seconds: float) -> float:
    """Events per second over a window; the window must be positive."""
    if seconds <= 0:
        raise ValueError(f"rate over a window of {seconds} s")
    return count / seconds


def spread(values) -> float:
    """Interquartile distance as a share of the median, with the
    quartiles of ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / med
