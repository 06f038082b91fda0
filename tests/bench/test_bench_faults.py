"""Runs with the timed path broken underneath come out not correct: the
harness's look for a chip is skipped (the CPU rehearsal) and the rest of
a run is driven as it is on the chip. One fault each: a token altered
where it is sampled; a decode step that hands back the KV pool it was
given; half of the decode batch left out. (The exchange between chips
does not exist on one chip.)"""

import functools
import pathlib

import jax
import pytest

from bench.run import run_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
SECONDS = 0.5

# one cell per configuration: the faults break the program's timed path,
# which every cell of a configuration shares
FAULT_CELLS = ("smollm_360m.chat", "smollm_360m_w2.batch")


def _fault_run(cell):
    # fewer requests than a full rehearsal: the check, not the tails
    rate = 100.0 if cell.endswith(".chat") else None
    return run_cell(ROOT, cell, 13, SECONDS, False, rehearsal=True,
                    rate_per_s=rate, strict=False)


def _break_decode(monkeypatch, broken):
    """Swap ``broken(logits, k_in, v_in, k_out, v_out)`` into both decode
    steps (plain and budgeted) the scheduler builds."""
    from repro.runtime import scheduler
    from repro.runtime.residency import executor

    def wrap(make):
        @functools.lru_cache(maxsize=None)
        def build(*key):
            step = jax.jit(make(*key))

            def run(params, token, k, v, rows, lengths):
                logits, k2, v2 = step(params, token, k, v, rows, lengths)
                return broken(logits, k, v, k2, v2)

            return run

        return build

    monkeypatch.setattr(scheduler, "_jitted_decode",
                        wrap(scheduler.make_paged_serve_step))
    monkeypatch.setattr(executor, "cached_budgeted_step",
                        wrap(executor.make_budgeted_paged_serve_step))


@pytest.mark.parametrize("cell", FAULT_CELLS)
def test_altered_token_is_caught(monkeypatch, cell):
    from repro.runtime import scheduler

    orig = scheduler.Scheduler._sample_one

    def altered(self, req, row):
        tok = orig(self, req, row)
        return (tok + 1) % self.cfg.vocab if len(req.output) == 2 else tok

    monkeypatch.setattr(scheduler.Scheduler, "_sample_one", altered)
    assert not _fault_run(cell)["correct"]


@pytest.mark.parametrize("cell", FAULT_CELLS)
def test_unchanged_kv_state_is_caught(monkeypatch, cell):
    # the step hands back the pool it was given: no new token's K/V row
    _break_decode(monkeypatch, lambda lg, k, v, k2, v2: (lg, k, v))
    assert not _fault_run(cell)["correct"]


@pytest.mark.parametrize("cell", FAULT_CELLS)
def test_half_the_lanes_left_out_is_caught(monkeypatch, cell):
    # the second half of the decode batch gets the first half's logits
    def half(lg, k, v, k2, v2):
        b = lg.shape[0] // 2
        return lg.at[b:2 * b].set(lg[:b]), k2, v2

    _break_decode(monkeypatch, half)
    assert not _fault_run(cell)["correct"]
