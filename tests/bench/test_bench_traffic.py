"""Each traffic mix gives every seed the same work, in its own order, and
keeps to its stated ranges."""

import pathlib
from collections import Counter

import numpy as np
import pytest

from bench.core.traffic import (
    exponential_gaps, generate, load_mix, sizes, zipf_counts,
)

ROOT = pathlib.Path(__file__).resolve().parents[2]
MIXES = sorted(p.stem for p in (ROOT / "bench/traffic").glob("*.json"))
SEEDS = (7, 2**31 + 12345)


def _gen(name, seed, seconds=40.0):
    mix = load_mix(ROOT / "bench/traffic" / f"{name}.json")
    return mix, generate(mix, seed, seconds, vocab=49152, max_len=2048)


@pytest.mark.parametrize("name", MIXES)
def test_deterministic_per_seed(name):
    _, a = _gen(name, SEEDS[1])
    _, b = _gen(name, SEEDS[1])
    assert len(a.requests) == len(b.requests)
    for x, y in zip(a.requests, b.requests):
        assert x.due == y.due and x.max_new == y.max_new
        assert np.array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("name", MIXES)
def test_same_work_in_another_order(name):
    _, a = _gen(name, SEEDS[0])
    _, b = _gen(name, SEEDS[1])

    def shape(w):
        pre = [w.prefix_lengths[r.prefix_id] if r.prefix_id >= 0 else 0
               for r in w.requests]
        return (sorted(len(r.prompt) - p for r, p in zip(w.requests, pre)),
                sorted(pre), sorted(r.max_new for r in w.requests))

    assert shape(a) == shape(b)
    assert [len(r.prompt) for r in a.requests] != [
        len(r.prompt) for r in b.requests]
    if a.loop == "open":
        # the gaps between arrivals are all but one of the same n gaps
        every = Counter(np.round(exponential_gaps(
            a.rate_per_s, len(a.requests), 40.0), 9))
        for w in (a, b):
            gaps = Counter(np.round(np.diff([r.due for r in w.requests]), 9))
            assert not gaps - every


@pytest.mark.parametrize("name", MIXES)
def test_within_stated_ranges(name):
    mix, w = _gen(name, SEEDS[0])
    pre = mix.get("prefix")
    for r in w.requests:
        body = len(r.prompt)
        if r.prefix_id >= 0:
            plen = w.prefix_lengths[r.prefix_id]
            assert pre["length"]["min"] <= plen <= pre["length"]["max"]
            body -= plen
        assert mix["prompt"]["min"] <= body <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= r.max_new <= mix["output"]["max"]
        assert len(r.prompt) + r.max_new <= 2048
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 49152
    if mix["loop"] == "open":
        assert len(w.requests) == round(mix["rate_per_s"] * 40.0)
        assert all(0 <= r.due < 40.0 for r in w.requests)
    else:
        assert len(w.requests) == mix["requests"]


def test_quantile_draws():
    x = sizes({"dist": "lognormal", "median": 128, "sigma": 1.0,
               "min": 16, "max": 512}, 1001)
    assert x[500] == 128 and x.min() == 16 and x.max() == 512
    g = exponential_gaps(5.0, 200, 40.0)
    assert g.sum() == pytest.approx(40.0)
    c = zipf_counts(8, 1.1, 320)
    assert c.sum() == 320 and list(c) == sorted(c, reverse=True)
