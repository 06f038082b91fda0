"""Percentile, rate and spread arithmetic of the benchmark."""

import math

import pytest

from bench.core.stats import (
    MIN_BEYOND, TooFewSamples, min_samples, percentile, rate, spread,
)


def test_tail_needs_ten_samples_beyond_it():
    assert MIN_BEYOND == 10
    assert min_samples(50) == 1
    assert min_samples(95) == 200
    assert min_samples(99) == 1000
    percentile(range(200), 95)
    with pytest.raises(TooFewSamples):
        percentile(range(199), 95)


def test_nearest_rank_reads_a_value_that_occurred():
    xs = list(range(1, 201))  # 1..200
    assert percentile(xs, 95) == 190
    assert percentile(xs, 50) == 100
    assert percentile([7.0], 50) == 7.0


def test_missing_values_count_as_missing_every_limit():
    xs = [1.0] * 189 + [None] * 11
    assert percentile(xs, 95) == math.inf
    assert percentile([1.0] * 190 + [None] * 10, 95) == 1.0


def test_rate_and_spread():
    assert rate(300, 40.0) == 7.5
    with pytest.raises(ValueError):
        rate(1, 0.0)
    # quartiles of 1..7 by statistics.quantiles: 2 and 6, median 4
    assert spread([1, 2, 3, 4, 5, 6, 7]) == pytest.approx(1.0)
