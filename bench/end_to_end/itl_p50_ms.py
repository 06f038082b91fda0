"""Median of every inter-token gap that ends inside the window, over all
requests: the host time between two consecutive tokens of a request."""

from bench.core.itl import window_gaps_ms
from bench.core.stats import percentile


def read(run):
    return percentile(window_gaps_ms(run), 50)
