"""The harness end to end on the CPU, at each configuration's rehearsal
sizes: every cell runs and reads correct, its traced run reads the
per-layer metrics that need no device, and the lower-precision control
comes out not correct."""

import dataclasses
import pathlib

import pytest

from bench.core import registry
from bench.core.config import load_config
from bench.core.engine import (
    build_engine, program_config, warm_up, warmup_requests,
)
from bench.core.traffic import generate, load_mix
from bench.reference.llama import make_weights
from bench.run import run_cell

ROOT = pathlib.Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in registry.load_benchmark(ROOT)["workloads"]]
SECONDS = 0.5


def _run(cell, seed=11, **kw):
    # a loaded CPU may finish too few requests for a tail: leave it out
    return run_cell(ROOT, cell, seed, SECONDS, False, rehearsal=True,
                    strict=False, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct(cell):
    res = _run(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    assert res["window"]["compiles"] == 0
    names = {m["name"] for m in registry.find_cell(ROOT, cell).end_to_end}
    assert {"setup_s", "itl_p50_ms"} <= set(res["metrics"]) <= names
    assert list(res)[-1] == "checks"


def test_traced_run_reads_host_side_metrics():
    res = run_cell(ROOT, "smollm_360m.chat", 12, SECONDS, True,
                   rehearsal=True, strict=False)
    assert res["correct"]
    assert res["metrics"]["prefix.hit_share"]["value"] > 0
    # no device here: device numbers are left out, never made up
    for name in ("step.decode_ms", "device.idle_share", "model.mfu"):
        assert name not in res["metrics"]


def test_control_is_not_correct():
    res = _run("smollm_360m.chat", control=True)
    limit = res["checks"]["gap_max"]["limit"]
    assert res["checks"]["gap_max"]["value"] <= limit
    assert res["control"]["gap_max"] > limit


@pytest.mark.parametrize("block_tokens", [8, 16])
def test_warm_up_copies_a_partly_matched_block(block_tokens):
    """A prompt that begins with part of a cached block takes a
    copy-on-write of that block; warm-up runs it once, so that no window
    compiles the copy (16 is the configurations' block size)."""
    cfg = load_config(ROOT / "bench/configs/smollm_360m.json",
                      "smollm_360m", rehearsal=True)
    cfg = dataclasses.replace(cfg, serving=dataclasses.replace(
        cfg.serving, block_tokens=block_tokens))
    mix = load_mix(ROOT / "bench/traffic/doc.json", rehearsal=True)
    work = generate(mix, 3, SECONDS, cfg.sizes.vocab, cfg.serving.max_len)
    weights = make_weights(cfg.sizes, 3, program_config(cfg).padded_vocab)
    sched = build_engine(cfg, weights, 3)
    warm_up(sched, warmup_requests(
        work, cfg.serving.prefill_chunk, block_tokens, cfg.sizes.vocab, 3,
        prefix_cache=True,
    ))
    assert sched.pool.cow_copies == 1
