"""The reader of ``kv.decode_read_share`` on synthetic runs, with the
cases in which it stays silent, and on the records a scheduler writes
while it decodes."""

import pathlib
import time

import numpy as np
import pytest

from bench.core import registry
from bench.core.record import Run
from bench.core.window import Window

ROOT = pathlib.Path(__file__).resolve().parents[2]


def read(spans, t_open=0.0, t_close=100.0):
    window = Window(seconds=t_close - t_open, t_open=t_open, t_close=t_close)
    run = Run(cell="c", cfg=None, workload=None, window=window, setup_s=0.0,
              peaks=None, spans=spans)
    return registry.reader(ROOT, "metrics", "kv.decode_read_share").read(run)


def dispatch(t1, blocks=None, table=64):
    rec = {"kind": "span", "phase": "round.decode_dispatch", "t0": t1 - 1,
           "t1": t1, "round": 0}
    if blocks is not None:
        rec.update(kv_blocks=blocks, kv_table_blocks=table)
    return rec


def test_share_is_blocks_read_over_table_entries_in_the_window():
    spans = [dispatch(10, 4), dispatch(20, 12), dispatch(150, 64),
             {"kind": "span", "phase": "round.logits_fetch", "t0": 20,
              "t1": 21, "round": 0}]
    assert read(spans) == pytest.approx(100.0 * 16 / 128)


@pytest.mark.parametrize("spans", [
    None,  # untraced run
    [],  # no decode step at all
    [dispatch(150, 8)],  # decode steps, none in the window
    [dispatch(10), dispatch(20)],  # a program that records no count
], ids=["untraced", "no_steps", "outside_window", "no_count"])
def test_share_is_silent_without_counted_steps_in_the_window(spans):
    assert read(spans) is None


@pytest.mark.parametrize("path", ["kernel", "reference"])
def test_share_reads_what_the_scheduler_counts(path, monkeypatch):
    """A scheduler with a tracked recorder counts, per decode step, the
    blocks its decode path reads against every lane's whole table: with
    the kernel (interpret mode), every lane's live blocks (a lane with
    nothing held reads the scratch block); with the reference, which
    gathers every table entry, all of them."""
    import functools

    import jax

    from repro.configs import get_smoke_config
    from repro.kernels import ops
    from repro.kernels import paged_attention as pa
    from repro.models import lm
    from repro.runtime import scheduler
    from repro.runtime.kv_pool import KVPool
    from repro.runtime.scheduler import Scheduler
    from repro.runtime.spans import SpanRecorder
    from repro.runtime.tracker import MemoryTracker

    calls = []
    if path == "kernel":
        def kernel(*args, **kw):
            calls.append(1)
            return pa.paged_decode(*args, **kw, interpret=True)

        monkeypatch.setattr(
            ops, "paged_decode_runs_kernel", lambda hd, pool: True
        )
        monkeypatch.setattr(ops, "paged_decode", kernel)
    cfg = get_smoke_config("smollm_360m")
    params = lm.init_params(cfg, jax.random.key(0))
    slots, max_len, t = 3, 64, 8
    pool = KVPool.for_slots(cfg, slots=slots, max_len=max_len, block_tokens=t)
    scheduler._jitted_decode.cache_clear()
    try:
        sched = Scheduler(cfg, params, pool, slots=slots, max_len=max_len)
        mem = MemoryTracker()
        sched.spans = SpanRecorder(time.monotonic, tracker=mem)
        rng = np.random.default_rng(0)
        for n, new in ((5, 6), (17, 3)):
            sched.submit(
                rng.integers(0, cfg.vocab, size=n).astype(np.int32), new
            )
        sched.run()
    finally:
        scheduler._jitted_decode.cache_clear()
    assert bool(calls) == (path == "kernel")
    steps = sched.stats.decode_steps
    assert steps > 0
    table = steps * slots * max_len // t
    if path == "kernel":
        # lane 0 decodes from 5 to 10 held tokens, lane 1 from 17 to 19;
        # each step reads ceil((length + 1) / 8) blocks, the idle lane one
        lane0 = [-(-(n + 1) // t) for n in range(5, 10)]
        lane1 = [-(-(n + 1) // t) for n in range(17, 19)] + [1] * 3
        want = sum(lane0) + sum(lane1) + steps
    else:
        want = table
    assert sched.stats.kv_blocks_read == want
    share = read(mem.spans, t_open=0.0, t_close=time.monotonic() + 1)
    assert share == pytest.approx(100.0 * want / table)
