"""95th percentile of every inter-token gap that ends inside the window."""

from bench.core.itl import window_gaps_ms
from bench.core.stats import percentile


def read(run):
    return percentile(window_gaps_ms(run), 95)
