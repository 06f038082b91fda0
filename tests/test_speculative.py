"""Speculative decoding: draft-tree verification over the paged pool.

The hard invariant is *structural token identity*: whatever the drafter
proposes, the verifier samples each position from the target's own
logits with the non-speculative rng key (seed, rid, position), so the
served stream is byte-identical to plain decode — drafter quality moves
the acceptance rate, never the output. The property sweep drives random
(seed, depth, acceptance-pattern) draft trees through a protocol-level
drafter that mixes oracle and deliberately-wrong proposals, checking
identity, pool refcount/ledger exactness after every rollback, and the
accepted-token conservation law.
"""

import dataclasses
import functools
import math

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro.configs import get_smoke_config
from repro.models import lm
from repro.runtime.kv_pool import KVPool
from repro.runtime.memledger import GAUGES, MemLedger, _snapshot
from repro.runtime.scheduler import Scheduler
from repro.runtime.speculative import (
    MODEL_DRAFT_FAMILIES,
    NgramDrafter,
    SpecConfig,
    Speculator,
    build_speculator,
    compatible_drafters,
    dequantize_ffn_params,
    pack_ffn_params,
    resolve,
)
from repro.runtime.tracker import DELTA_KEYS, MemoryTracker, delta_coverage_gaps

BLOCK, MAX_LEN, SLOTS, P, GEN = 4, 32, 2, 6, 8
N_REQ = 3  # > SLOTS so one request staggers in behind the others


@functools.lru_cache(maxsize=None)
def _ctx(arch="smollm_360m"):
    cfg = get_smoke_config(arch)
    return cfg, lm.init_params(cfg, jax.random.key(0))


def _pool(cfg):
    return KVPool(
        cfg, n_blocks=1 + SLOTS * MAX_LEN // BLOCK, block_tokens=BLOCK
    )


def _sched(cfg, params, **kw):
    kw.setdefault("slots", SLOTS)
    kw.setdefault("max_len", MAX_LEN)
    return Scheduler(cfg, params, _pool(cfg), **kw)


def _prompts(n, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, vocab, size=(P,)).astype(np.int32) for _ in range(n)
    ]


def _baseline(cfg, params, prompts, sampling):
    sched = _sched(cfg, params, sampling=sampling)
    for p in prompts:
        sched.submit(p, GEN)
    sched.run()
    return sched.outputs()


class PatternDrafter:
    """Protocol-level drafter for the property sweep: proposes the token
    the non-speculative oracle stream holds at each position with
    probability ``q``, else a token guaranteed wrong — so a random
    acceptance pattern exercises every accept length from 1 (pending
    only) to the full chain, without any model cost."""

    is_model = False

    def __init__(self, oracle, vocab, q, seed):
        self.oracle = oracle  # rid -> the full non-speculative output
        self.vocab = vocab
        self.q = q
        self.rng = np.random.default_rng(seed)

    def start_lane(self, slot, prompt):
        return 0, 0

    def release_lane(self, slot):
        pass

    def accept(self, slot, n_rows):
        pass

    def propose(self, lanes, k, sampling):
        props = np.zeros((len(lanes), k - 1), np.int32)
        for j, ln in enumerate(lanes):
            out = self.oracle[ln.rid]
            for m in range(k - 1):
                pos = ln.out_len + m
                right = int(out[pos]) if pos < len(out) else 0
                if self.rng.random() < self.q:
                    props[j, m] = right
                else:  # anything in the vocab except the oracle token
                    wrong = int(self.rng.integers(self.vocab - 1))
                    props[j, m] = (right + 1 + wrong) % self.vocab
        return props, 0


def _integrated_ledger_state(records):
    """Fold the attach baseline + every d_ delta, as validate_ledger
    does, returning the integrated gauge dict."""
    assert records and records[0]["op"] == "attach"
    state = {k: records[0][k] for k in GAUGES}
    for r in records[1:]:
        if r.get("op") == "reserve":
            continue
        for k in GAUGES:
            state[k] += r.get("d_" + k, 0)
    return state


# ---------------- the property sweep ----------------


@settings(max_examples=6, deadline=None)
@given(
    seed=st.integers(0, 5),
    depth=st.sampled_from((2, 3, 5)),
    q=st.sampled_from((0.0, 0.35, 0.75, 1.0)),
    greedy=st.booleans(),
)
def test_random_draft_trees_are_token_identical(seed, depth, q, greedy):
    cfg, params = _ctx()
    sampling = (
        lm.SamplingParams()
        if greedy
        else lm.SamplingParams(temperature=0.9, top_k=32, seed=seed)
    )
    prompts = _prompts(N_REQ, cfg.vocab, seed=seed)
    oracle = _baseline(cfg, params, prompts, sampling)

    tracker = MemoryTracker()
    ledger = MemLedger(lambda: 0.0, tracker=tracker)
    sched = _sched(
        cfg,
        params,
        sampling=sampling,
        speculative=Speculator(
            PatternDrafter(oracle, cfg.vocab, q, seed), depth=depth
        ),
        ledger=ledger,
    )
    for p in prompts:
        sched.submit(p, GEN)
    while sched.queue or any(r is not None for r in sched.active):
        sched.round()
        # rollback exactness, probed after every round: refcounts audit
        # clean and no draft-class block outlives its verify cycle
        sched.pool.validate()
        assert not sched.pool.draft_rids()

    assert sched.outputs() == oracle, (
        f"speculative stream diverged (depth={depth}, q={q}, "
        f"greedy={greedy})"
    )

    # accepted-token conservation: every decode token flowed through a
    # verify step (the first token of each request comes from prefill)
    stats = sched.stats
    assert stats.accepted_tokens == N_REQ * (GEN - 1)
    # a verify step is ONE batched cycle across every decoding lane, so
    # the bounds are per-cycle: a request needs at least ceil((GEN-1)/
    # depth) cycles of its own, and the worst case is one token per
    # cycle with no lane overlap at all
    per_req = math.ceil((GEN - 1) / depth)
    assert per_req <= stats.verify_steps <= N_REQ * (GEN - 1)
    if q == 1.0:  # every chain accepted whole
        assert stats.verify_steps <= N_REQ * per_req
    if q == 0.0:  # every proposal rejected: one token per lane-cycle
        assert stats.verify_steps >= GEN - 1
    assert stats.draft_tokens > 0

    # ledger exactness: integrating the draft_grow/draft_end deltas (and
    # everything else) lands int-exactly on the live pool snapshot
    ledger.sync()
    ledger.flush()
    recs = tracker.mems
    assert _integrated_ledger_state(recs) == _snapshot(sched.pool)
    # decode-time block growth goes through the draft owner class
    assert any(r.get("op") == "draft_grow" for r in recs)


# ---------------- drafter units ----------------


def test_ngram_drafter_continuation():
    d = NgramDrafter()
    ctx = np.array([7, 1, 2, 3, 9, 1, 2], np.int32)
    # suffix [1, 2] last occurred at index 1 -> continuation 3, 9
    np.testing.assert_array_equal(d._continuation(ctx, 2), [3, 9])
    # no earlier occurrence of anything: repeat-last fallback
    np.testing.assert_array_equal(
        d._continuation(np.array([4, 5, 6], np.int32), 3), [6, 6, 6]
    )
    # match runs to end of context: continuation crosses into the suffix
    ctx2 = np.array([1, 2, 8, 1, 2], np.int32)
    np.testing.assert_array_equal(d._continuation(ctx2, 3), [8, 1, 2])
    # continuation shorter than n: padded with its own last token
    ctx3 = np.array([3, 7, 3], np.int32)
    np.testing.assert_array_equal(d._continuation(ctx3, 3), [7, 3, 3])


def test_ngram_speculation_token_identical_seeded():
    cfg, params = _ctx()
    sampling = lm.SamplingParams(temperature=0.8, top_k=40, seed=3)
    prompts = _prompts(N_REQ, cfg.vocab, seed=21)
    oracle = _baseline(cfg, params, prompts, sampling)
    spec = build_speculator(
        cfg,
        params,
        SpecConfig(drafter="ngram", depth=4),
        slots=SLOTS,
        max_len=MAX_LEN,
        smoke=True,
    )
    sched = _sched(cfg, params, sampling=sampling, speculative=spec)
    for p in prompts:
        sched.submit(p, GEN)
    sched.run()
    assert sched.outputs() == oracle
    assert sched.stats.accepted_tokens == N_REQ * (GEN - 1)


def test_model_drafter_twin_token_identical():
    cfg, params = _ctx()
    # the lossless pairing: a dequantized target and its re-packed twin
    params = dequantize_ffn_params(params, 2)
    prompts = _prompts(N_REQ, cfg.vocab, seed=8)
    oracle = _baseline(cfg, params, prompts, None)
    spec = build_speculator(
        cfg,
        params,
        SpecConfig(drafter="smollm_360m", depth=4, quant=2),
        slots=SLOTS,
        max_len=MAX_LEN,
        smoke=True,
    )
    assert spec.is_model and spec.name.endswith("@w2")
    sched = _sched(cfg, params, speculative=spec)
    for p in prompts:
        sched.submit(p, GEN)
    sched.run()
    assert sched.outputs() == oracle
    # the twin's logits equal the target's, so every chain is accepted
    # whole: no request ever needs more than ceil((GEN-1)/depth) cycles
    assert sched.stats.verify_steps <= N_REQ * math.ceil((GEN - 1) / 4)


def test_drafter_lane_past_max_len_leaves_other_lanes_intact(monkeypatch):
    """A drafter rolls every lane ``k`` steps, so a lane with one token
    left (started at max_len - 2) writes rows at and past max_len. Through
    the paged decode kernel (interpret mode) those rows go to the scratch
    block: a short lane drafted beside it proposes the same tokens and
    holds the same blocks as when it drafts beside a short neighbour."""
    from repro.kernels import ops
    from repro.kernels import paged_attention as pa
    from repro.runtime import speculative
    from repro.runtime.speculative import LaneDraft, ModelDrafter

    cfg, params = _ctx()
    monkeypatch.setattr(
        ops, "paged_decode_runs_kernel", lambda hd, pool: True
    )
    monkeypatch.setattr(
        ops, "paged_decode",
        functools.partial(pa.paged_decode, interpret=True),
    )
    speculative._jitted_draft_decode.cache_clear()
    max_len, k = 32, 4
    rng = np.random.default_rng(3)
    # past its first block, which a neighbour's stray row would overwrite
    short = rng.integers(0, cfg.vocab, size=20).astype(np.int32)
    full = rng.integers(0, cfg.vocab, size=max_len - 2).astype(np.int32)

    def draft(neighbour):
        # lanes 0 and 2 (the last) hold ``neighbour``, lane 1 ``short``
        drafter = ModelDrafter(cfg, params, slots=3, max_len=max_len)
        prompts = (neighbour, short, neighbour)
        lanes = []
        for slot, prompt in enumerate(prompts):
            drafter.start_lane(slot, prompt)
            lanes.append(LaneDraft(slot, slot, int(prompt[-1]), 0,
                                   len(prompt), prompt))
        props, _ = drafter.propose(lanes, k, lm.SamplingParams())
        blocks = drafter._block_table[1]
        return props[1], np.asarray(drafter.k[:, blocks]), np.asarray(
            drafter.v[:, blocks]
        )

    try:
        got, want = draft(full), draft(short)
    finally:
        speculative._jitted_draft_decode.cache_clear()
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_twin_packing_round_trips_on_its_own_codebook():
    cfg, params = _ctx()
    dense = dequantize_ffn_params(params, 2)
    first = pack_ffn_params(params, 2)
    again = pack_ffn_params(dense, 2)
    for k in ("w1", "w3", "w2"):
        # re-quantizing the dequantized twin reproduces the codes exactly
        # (the codebook is a fixed point); the recomputed scale only
        # drifts by float-sum epsilon
        np.testing.assert_array_equal(
            np.asarray(first["layers"][k]["packed"]),
            np.asarray(again["layers"][k]["packed"]),
        )
        np.testing.assert_allclose(
            np.asarray(first["layers"][k]["scale"]),
            np.asarray(again["layers"][k]["scale"]),
            rtol=1e-5,
        )


# ---------------- pool draft bracket ----------------


def test_pool_draft_bracket_grow_and_rollback():
    cfg, _ = _ctx()
    pool = _pool(cfg)
    tracker = MemoryTracker()
    ledger = MemLedger(lambda: 0.0, tracker=tracker)
    ledger.attach(pool)
    pool.admit(0, P + GEN)
    pool.note_tokens(0, P)
    held = pool.blocks_held(0)
    free = pool.free_blocks

    pool.begin_draft(0, P + 5)  # grows across a block boundary
    assert set(pool.draft_rids()) == {0}
    assert pool.blocks_held(0) > held
    pool.validate()  # draft growth keeps the refcount audit clean

    pool.end_draft(0, P + 1)  # chain rejected: keep only the pending row
    assert not pool.draft_rids()
    assert pool.free_blocks == free  # surplus blocks all returned
    pool.validate()

    # ledger integrates to the live snapshot across the bracket
    ledger.sync()
    ledger.flush()
    mems = tracker.mems
    assert any(r["op"] == "draft_grow" for r in mems)
    assert any(r["op"] == "draft_end" for r in mems)
    assert _integrated_ledger_state(mems) == _snapshot(pool)

    pool.release(0)
    pool.validate()
    assert pool.free_blocks == pool.usable_blocks


def test_release_clears_open_draft_bracket():
    cfg, _ = _ctx()
    pool = _pool(cfg)
    pool.admit(0, P + GEN)
    pool.note_tokens(0, P)
    pool.begin_draft(0, P + 4)
    pool.release(0)  # drain/abort path: bracket still open
    assert not pool.draft_rids()
    pool.validate()
    assert pool.free_blocks == pool.usable_blocks


# ---------------- resolution ----------------


def test_resolve_rejects_unknown_drafter_listing_options():
    cfg, _ = _ctx()
    with pytest.raises(ValueError, match="ngram"):
        resolve(cfg, SpecConfig(drafter="no_such_arch"), smoke=True)


def test_resolve_rejects_unpackable_drafter_family():
    cfg, _ = _ctx()
    with pytest.raises(ValueError, match="packed twin"):
        resolve(cfg, SpecConfig(drafter="olmoe_1b_7b"), smoke=True)


def test_resolve_rejects_vocab_mismatch():
    cfg, _ = _ctx()
    target = dataclasses.replace(cfg, vocab=cfg.vocab + 1)
    with pytest.raises(ValueError, match="vocab"):
        resolve(target, SpecConfig(drafter="smollm_360m"), smoke=True)


def test_resolve_rejects_hybrid_target():
    hybrid = get_smoke_config("zamba2_2p7b")
    with pytest.raises(ValueError, match="roll back"):
        resolve(hybrid, SpecConfig(drafter="ngram"), smoke=True)


def test_resolve_rejects_bad_depth_and_quant():
    cfg, _ = _ctx()
    with pytest.raises(ValueError, match="depth"):
        resolve(cfg, SpecConfig(drafter="ngram", depth=1), smoke=True)
    with pytest.raises(ValueError, match="carrier"):
        resolve(cfg, SpecConfig(drafter="ngram", quant=4), smoke=True)


def test_compatible_drafters_cover_packable_families():
    cfg, _ = _ctx()
    opts = compatible_drafters(cfg, smoke=True)
    assert opts[0] == "ngram"
    assert "smollm_360m" in opts  # the twin itself
    for arch in opts[1:]:
        assert get_smoke_config(arch).family in MODEL_DRAFT_FAMILIES


def test_moe_target_has_no_twin_drafter():
    mcfg = get_smoke_config("olmoe_1b_7b")
    opts = compatible_drafters(mcfg, smoke=True)
    # ngram and *foreign* packable archs, never the moe arch itself
    # (expert FFNs do not pack into FCMP carriers)
    assert "ngram" in opts and "olmoe_1b_7b" not in opts
    rs = resolve(mcfg, SpecConfig(drafter="ngram"), smoke=True)
    assert rs.draft_cfg is None and not rs.twin


# ---------------- telemetry coverage ----------------


def test_spec_counters_are_replayable_deltas():
    for key in ("accepted_tokens", "draft_tokens", "verify_steps"):
        assert key in DELTA_KEYS
    assert delta_coverage_gaps() == []
