"""Continuous-batching subsystem: KV-pool invariants, token-budget
admission, request lifecycle ordering, queue draining, and decode-output
equivalence between the pool-indexed serve step and the per-slot ring
path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_smoke_config
from repro.models import lm
from repro.runtime.kv_pool import KVPool, choose_block_tokens
from repro.runtime.scheduler import RequestState, Scheduler
from repro.runtime.steps import make_serve_step

# one shared geometry so every test reuses the same jit traces
BLOCK, MAX_LEN, SLOTS, P, GEN = 4, 16, 2, 4, 4


def _cfg():
    return get_smoke_config("smollm_360m")


def _pool(cfg, n_blocks=1 + SLOTS * MAX_LEN // BLOCK):
    return KVPool(cfg, n_blocks=n_blocks, block_tokens=BLOCK)


def _sched(cfg, params, **kw):
    kw.setdefault("slots", SLOTS)
    kw.setdefault("max_len", MAX_LEN)
    return Scheduler(cfg, params, _pool(cfg), **kw)


def _prompts(n, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=(P,)).astype(np.int32) for _ in range(n)]


# ---------------- pool allocator invariants ----------------


def test_pool_alloc_free_invariants():
    pool = _pool(_cfg(), n_blocks=9)  # 8 usable blocks
    pool.admit(0, 16)  # 4-block commitment
    pool.admit(1, 12)  # 3-block commitment
    for n in range(1, 17):
        pool.note_tokens(0, n)
        pool.validate()
    pool.note_tokens(1, 12)
    pool.validate()
    blocks0, blocks1 = pool.blocks_of(0), pool.blocks_of(1)
    assert len(set(blocks0) & set(blocks1)) == 0
    assert len(blocks0) * BLOCK == 16 and len(blocks1) * BLOCK == 12
    st = pool.stats()
    assert st.held_tokens == 28 and st.held_blocks == 7
    assert st.utilization == 28 / 28  # both requests exactly fill blocks

    # exceeding the commitment is an error, not silent growth
    with pytest.raises(RuntimeError):
        pool.note_tokens(0, 17)

    # full reclamation
    pool.release(0)
    pool.release(1)
    pool.validate()
    assert pool.free_blocks == pool.usable_blocks
    assert pool.live_requests() == []


def test_pool_admission_respects_outstanding_commitment():
    pool = _pool(_cfg(), n_blocks=9)  # 8 usable
    pool.admit(0, 16)  # commits 4 blocks, holds 0
    assert pool.free_blocks == 8
    assert not pool.can_admit(17)  # 5 blocks > 8 - 4 uncommitted
    assert pool.can_admit(16)
    with pytest.raises(RuntimeError):
        pool.admit(1, 17)
    with pytest.raises(ValueError):
        pool.admit(0, 4)  # double admit


def test_pool_fragmentation_report_and_block_chooser():
    pool = _pool(_cfg(), n_blocks=17)
    for rid, tokens in enumerate([5, 7, 9]):
        pool.admit(rid, tokens)
        pool.note_tokens(rid, tokens)
    rep = pool.fragmentation_report()
    # FFD tail-sharing can only save blocks vs private placement (Eq. 1)
    assert rep["ffd_blocks"] <= rep["baseline_blocks"]
    assert rep["ffd_efficiency"] >= rep["baseline_efficiency"]

    # growth-aware sweep: short-lived caches want fine blocks, long ones
    # amortise per-block overhead with coarser blocks
    assert choose_block_tokens([32]) <= choose_block_tokens([512])
    assert choose_block_tokens([32]) in (4, 8, 16, 32, 64)


# ---------------- scheduler lifecycle ----------------


@pytest.fixture(scope="module")
def served():
    """One drained scheduler shared by the lifecycle/drain assertions."""
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    sched = _sched(cfg, params)
    n = 5  # n % slots != 0: the legacy tail-drop regression shape
    for prompt in _prompts(n, cfg.vocab):
        sched.submit(prompt, GEN)
    stats = sched.run()
    return sched, stats, n


def test_scheduler_drains_queue_with_ragged_tail(served):
    """Regression: requests % batch != 0 must not drop the queue tail."""
    sched, stats, n = served
    assert stats.completed == n
    outputs = sched.outputs()
    assert sorted(outputs) == list(range(n))
    assert all(len(v) == GEN for v in outputs.values())
    assert sched.queue == type(sched.queue)()
    assert all(r is None for r in sched.active)


def test_request_lifecycle_ordering(served):
    sched, _, _ = served
    want = [
        RequestState.QUEUED,
        RequestState.PREFILL,
        RequestState.DECODE,
        RequestState.DONE,
    ]
    for req in sched.requests.values():
        assert req.states_seen == want
        assert req.t_first_token >= req.t_submit


def test_pool_fully_reclaimed_after_drain(served):
    sched, _, _ = served
    sched.pool.validate()
    assert sched.pool.free_blocks == sched.pool.usable_blocks
    assert sched.pool.stats().held_tokens == 0


def test_admission_respects_token_budget():
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    total = P + GEN
    # room for exactly one in-flight request
    sched = _sched(cfg, params, token_budget=total + total // 2)
    for prompt in _prompts(4, cfg.vocab):
        sched.submit(prompt, GEN)
    max_active = 0
    while sched.queue or any(r is not None for r in sched.active):
        sched.round()
        max_active = max(max_active, sum(r is not None for r in sched.active))
        assert sched.committed_tokens <= sched.token_budget
    assert max_active == 1
    assert sched.stats.completed == 4

    with pytest.raises(ValueError):  # over-budget requests rejected upfront
        sched.submit(np.zeros(MAX_LEN - 1, np.int32), GEN)


def test_eq2_default_decode_per_round():
    """R_F default mirrors gals Eq. 2: ceil(H_B / N_ports) decode rounds."""
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    assert _sched(cfg, params).decode_per_round == 1  # 2 slots / 2 ports
    pool = KVPool(cfg, n_blocks=1 + 5 * MAX_LEN // BLOCK, block_tokens=BLOCK)
    s5 = Scheduler(cfg, params, pool, slots=5, max_len=MAX_LEN)
    assert s5.decode_per_round == 3  # ceil(5/2)


# ---------------- paged step vs per-slot ring equivalence ----------------


def test_paged_decode_matches_ring_path():
    """Pool-indexed gather/scatter decode == the ring-cache decode path."""
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(1))
    b = SLOTS
    prompts = np.stack(_prompts(b, cfg.vocab, seed=3))  # (B, P)

    # ring path: teacher-force the prompt, then greedy-decode
    serve = jax.jit(make_serve_step(cfg))
    cache = lm.init_cache(cfg, b, MAX_LEN)
    for t in range(P):
        ring_logits, cache = serve(params, jnp.asarray(prompts[:, t : t + 1]), cache)

    # pool path: batched prefill into the pool, then paged decode
    pool = _pool(cfg)
    pre_logits, ks, vs = lm.prefill_with_cache(
        params, cfg, jnp.asarray(prompts), P - 1
    )
    for rid in range(b):
        pool.admit(rid, P + GEN)
        pool.write_prefill(rid, ks[:, rid], vs[:, rid])
    np.testing.assert_allclose(
        np.asarray(pre_logits), np.asarray(ring_logits), rtol=1e-4, atol=1e-4
    )

    n_table = pool.blocks_for(MAX_LEN)
    lengths = np.full((b,), P, np.int32)
    token = np.argmax(np.asarray(pre_logits[:, 0, :]), -1).astype(np.int32)
    pk, pv = pool.k, pool.v
    for _ in range(GEN):
        ring_logits, cache = serve(params, jnp.asarray(token[:, None]), cache)
        for rid in range(b):
            pool.note_tokens(rid, int(lengths[rid]) + 1)
        table = np.stack([pool.table_of(r, n_table) for r in range(b)])
        paged_logits, pk, pv = lm.decode_step_paged(
            params, cfg, jnp.asarray(token[:, None]), pk, pv,
            jnp.asarray(table), jnp.asarray(lengths),
        )
        np.testing.assert_allclose(
            np.asarray(paged_logits), np.asarray(ring_logits),
            rtol=1e-4, atol=1e-4,
        )
        token = np.argmax(np.asarray(paged_logits[:, 0, :]), -1).astype(np.int32)
        lengths += 1


def test_staggered_lanes_decode_independently():
    """Lanes at different depths coexist: a late-admitted request's output
    equals the same request served alone (per-lane positions, no lockstep)."""
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    prompts = _prompts(3, cfg.vocab, seed=9)

    def outputs_of(schedule):
        sched = _sched(cfg, params)
        for p in schedule:
            sched.submit(p, GEN)
        sched.run()
        return sched.outputs()

    together = outputs_of(prompts)  # 3 requests on 2 slots: req 2 staggers
    for i, p in enumerate(prompts):
        alone = outputs_of([p])
        assert together[i] == alone[0], f"request {i} diverged"


# ---------------- sampling (temperature / top-k / top-p) ----------------


def _run_sampled(cfg, params, prompts, sampling, gen=GEN):
    sched = _sched(cfg, params, sampling=sampling)
    for p in prompts:
        sched.submit(p, gen)
    sched.run()
    return sched.outputs()


def test_temperature_zero_is_greedy():
    """Greedy is exactly the temperature=0 special case of the sampler."""
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    prompts = _prompts(3, cfg.vocab, seed=11)
    greedy_default = _run_sampled(cfg, params, prompts, None)
    t0 = _run_sampled(cfg, params, prompts, lm.SamplingParams(temperature=0.0))
    k1 = _run_sampled(
        cfg, params, prompts, lm.SamplingParams(temperature=5.0, top_k=1)
    )
    assert greedy_default == t0 == k1


def test_sampling_is_seed_deterministic():
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    prompts = _prompts(3, cfg.vocab, seed=12)
    sp = lm.SamplingParams(temperature=0.9, top_k=40, top_p=0.95, seed=7)
    a = _run_sampled(cfg, params, prompts, sp)
    b = _run_sampled(cfg, params, prompts, sp)
    assert a == b, "same seed must replay identical tokens"
    c = _run_sampled(cfg, params, prompts, dataclasses.replace(sp, seed=8))
    assert a != c, "a different seed should perturb sampled output"
    g = _run_sampled(cfg, params, prompts, None)
    assert a != g, "temperature 0.9 should diverge from greedy"


def test_sampled_requests_independent_of_lane_placement():
    """The staggered-lane invariant extends to sampling: the rng is keyed
    on (seed, rid, position), not on lanes or co-residents — but rids are
    scheduler-local, so the 'alone' run must replay the same rid."""
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    prompts = _prompts(3, cfg.vocab, seed=13)
    sp = lm.SamplingParams(temperature=0.8, top_k=0, top_p=0.9, seed=3)
    together = _run_sampled(cfg, params, prompts, sp)

    sched = _sched(cfg, params, sampling=sp)
    sched.submit(prompts[0], GEN)  # rid 0
    sched.submit(prompts[1], GEN)  # rid 1
    sched.submit(prompts[2], GEN)  # rid 2: staggered behind the first two
    sched.run()
    assert sched.outputs() == together


def test_top_k_restricts_support():
    from repro.models.lm import SamplingParams, sample_logits

    rng = np.random.default_rng(0)
    row = np.array([0.0, 1.0, 2.0, 3.0, 10.0], np.float32)
    for _ in range(20):
        t = sample_logits(row, SamplingParams(temperature=1.0, top_k=2), rng)
        assert t in (3, 4)
        t = sample_logits(
            row, SamplingParams(temperature=1.0, top_p=1e-6), rng
        )
        assert t == 4  # nucleus always keeps >= 1 token
    # top_k >= V is unrestricted, not a numpy partition crash
    t = sample_logits(row, SamplingParams(temperature=1.0, top_k=99), rng)
    assert 0 <= t < len(row)
    # greedy never touches the rng (rng=None is legal)
    assert sample_logits(row, SamplingParams(), None) == 4


# ---------------- chunked prefill ----------------


def test_long_prompt_over_budget_is_chunked():
    """Regression (ISSUE 3): a prompt longer than the admission token
    budget must be admitted and split across scheduler rounds, not
    rejected and not prefilled in one monopolizing step — and its tokens
    must equal the single-shot prefill of a large-budget scheduler."""
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(21)
    max_len = 40
    long_p = rng.integers(0, cfg.vocab, size=(24,)).astype(np.int32)

    def run(budget, chunk=None):
        pool = KVPool(
            cfg, n_blocks=1 + 2 * max_len // BLOCK, block_tokens=BLOCK
        )
        sched = Scheduler(
            cfg, params, pool, slots=2, max_len=max_len,
            token_budget=budget, prefill_chunk=chunk,
        )
        sched.submit(long_p, GEN)
        stats = sched.run()
        return sched.outputs()[0], stats

    chunked, st_c = run(budget=16)  # 24-token prompt -> 16 + 8 chunks
    single, st_s = run(budget=64)
    assert st_s.prefill_steps == 1
    assert st_c.prefill_steps == 2, "prompt must split into budget chunks"
    assert chunked == single, "chunked prefill changed the tokens"
    assert st_c.completed == st_s.completed == 1


def test_chunked_prefill_coexists_with_decode():
    """Short requests admitted before a long prompt keep decoding while
    the long prompt chunks through its prefill rounds."""
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(22)
    max_len = 48
    pool = KVPool(cfg, n_blocks=1 + 3 * max_len // BLOCK, block_tokens=BLOCK)
    sched = Scheduler(
        cfg, params, pool, slots=3, max_len=max_len,
        token_budget=40, prefill_chunk=8,
    )
    short = _prompts(2, cfg.vocab, seed=23)
    long_p = rng.integers(0, cfg.vocab, size=(24,)).astype(np.int32)
    for p in short:
        sched.submit(p, GEN)
    sched.submit(long_p, GEN)
    stats = sched.run()
    assert stats.completed == 3
    assert stats.prefill_steps == 2 + 3  # 2 single-shot + 24/8 chunks
    outs = sched.outputs()
    assert all(len(v) == GEN for v in outs.values())
    # the long request's (greedy) output must match it running alone:
    # per-lane positions + pool-gathered chunk attention keep chunked
    # prefill independent of co-resident decode traffic
    alone_pool = KVPool(
        cfg, n_blocks=1 + 3 * max_len // BLOCK, block_tokens=BLOCK
    )
    alone = Scheduler(
        cfg, params, alone_pool, slots=3, max_len=max_len,
        token_budget=40, prefill_chunk=8,
    )
    alone.submit(long_p, GEN)
    alone.run()
    assert outs[2] == alone.outputs()[0]


def test_moe_over_budget_prompt_chunks_token_identical():
    """MoE prompts over the admission budget chunk like dense ones: the
    dropless per-token dispatch routes each token independently, so a
    chunk boundary is invisible to the expert gates and a budget-chunked
    prefill must emit exactly the single-shot token stream."""
    cfg = get_smoke_config("olmoe_1b_7b")
    params = lm.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(25)
    long_p = rng.integers(0, cfg.vocab, size=(24,)).astype(np.int32)

    def run(budget):
        pool = KVPool.for_slots(cfg, slots=2, max_len=64, block_tokens=BLOCK)
        sched = Scheduler(
            cfg, params, pool, slots=2, max_len=64, token_budget=budget
        )
        sched.submit(long_p, GEN)
        stats = sched.run()
        return sched.outputs()[0], stats

    chunked, st_c = run(budget=16)  # 24-token prompt -> 16 + 8 chunks
    single, st_s = run(budget=64)
    assert st_s.prefill_steps == 1
    assert st_c.prefill_steps == 2, "prompt must split into budget chunks"
    assert chunked == single, "chunked moe prefill changed the tokens"
    assert st_c.completed == st_s.completed == 1
    # the tally is a load signal, not an exact busy-token count: the
    # final chunk pads to the fixed chunk width, so chunking can only
    # add padded-row slots, never lose routed ones
    assert st_c.expert_tokens >= st_s.expert_tokens > 0


# ---------------- hybrid family on the paged pool ----------------


def test_hybrid_paged_matches_ring_path():
    """Zamba-style hybrids serve through the KV pool (ISSUE 4 satellite):
    the shared attention blocks page their KV while the SSM state stays
    lane-resident, and the token stream equals the ring-cache decode path
    replaying the prompt token-by-token."""
    cfg = get_smoke_config("zamba2_2p7b")
    assert cfg.family == "hybrid" and cfg.n_kv_cache_layers == 2
    params = lm.init_params(cfg, jax.random.key(0))
    prompt = _prompts(1, cfg.vocab, seed=31)[0]

    serve = jax.jit(make_serve_step(cfg))
    cache = lm.init_cache(cfg, 1, MAX_LEN)
    for t in range(P):
        ring_logits, cache = serve(
            params, jnp.asarray(prompt[None, t : t + 1]), cache
        )
    ref = [int(np.argmax(np.asarray(ring_logits[0, 0])))]
    for _ in range(GEN - 1):
        ring_logits, cache = serve(
            params, jnp.asarray(np.array([[ref[-1]]], np.int32)), cache
        )
        ref.append(int(np.argmax(np.asarray(ring_logits[0, 0]))))

    pool = KVPool.for_slots(
        cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
    )
    sched = Scheduler(cfg, params, pool, slots=SLOTS, max_len=MAX_LEN)
    sched.submit(prompt, GEN)
    stats = sched.run()
    assert stats.prefill_steps == 1  # single-shot unpadded prefill
    assert sched.outputs()[0] == ref


def test_hybrid_staggered_lanes_independent():
    """The staggered-lane invariant holds for hybrids too: lane-resident
    SSM state and pooled shared-attention KV keep co-residents from
    perturbing each other."""
    cfg = get_smoke_config("zamba2_2p7b")
    params = lm.init_params(cfg, jax.random.key(0))
    prompts = _prompts(3, cfg.vocab, seed=33)

    def outputs_of(schedule):
        pool = KVPool.for_slots(
            cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
        )
        sched = Scheduler(cfg, params, pool, slots=SLOTS, max_len=MAX_LEN)
        for p in schedule:
            sched.submit(p, GEN)
        sched.run()
        return sched.outputs()

    together = outputs_of(prompts)  # 3 requests on 2 slots: req 2 staggers
    for i, p in enumerate(prompts):
        assert together[i] == outputs_of([p])[0], f"request {i} diverged"


def test_hybrid_over_budget_prompt_chunks_token_identical():
    """Hybrid prompts over the admission budget chunk instead of being
    rejected (ISSUE 6): the carried-state suffix kernel makes chunk
    resume well-defined — each chunk integrates its SSD state and hands
    the lane to the next — so a budget-chunked prefill must emit exactly
    the single-shot token stream."""
    cfg = get_smoke_config("zamba2_2p7b")
    params = lm.init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(24)
    long_p = rng.integers(0, cfg.vocab, size=(24,)).astype(np.int32)

    def run(budget):
        pool = KVPool.for_slots(cfg, slots=2, max_len=64, block_tokens=BLOCK)
        sched = Scheduler(
            cfg, params, pool, slots=2, max_len=64, token_budget=budget
        )
        sched.submit(long_p, GEN)
        stats = sched.run()
        return sched.outputs()[0], stats

    chunked, st_c = run(budget=16)  # 24-token prompt -> 16 + 8 chunks
    single, st_s = run(budget=64)
    assert st_s.prefill_steps == 1
    assert st_c.prefill_steps == 2, "prompt must split into budget chunks"
    assert chunked == single, "chunked hybrid prefill changed the tokens"
    assert st_c.completed == st_s.completed == 1


def test_pool_rejects_pure_ssm_only():
    """After the hybrid satellite only attention-free ssm is outside the
    paged path."""
    ssm = get_smoke_config("mamba2_1p3b")
    with pytest.raises(ValueError, match="paged families"):
        KVPool(ssm, n_blocks=9, block_tokens=BLOCK)


def test_moe_padded_bucket_prefill_token_identical():
    """Dropless routing is padding-inert (per-token gates + causal
    attention keep the padded tail out of every real token's compute),
    so the scheduler block-rounds moe prompts into padded buckets like
    dense — and the first generated token must still equal the argmax of
    an unpadded reference prefill."""
    cfg = get_smoke_config("olmoe_1b_7b")
    params = lm.init_params(cfg, jax.random.key(0))
    prompt = _prompts(1, cfg.vocab, seed=7)[0][:3]  # 3 % BLOCK != 0
    pool = KVPool.for_slots(cfg, slots=2, max_len=MAX_LEN, block_tokens=BLOCK)
    sched = Scheduler(cfg, params, pool, slots=2, max_len=MAX_LEN)
    sched.submit(prompt, GEN)
    stats = sched.run()
    assert stats.completed == 1
    lg, _, _, _ = lm.prefill_with_cache(
        params, cfg, jnp.asarray(prompt[None]), len(prompt) - 1
    )
    ref_first = int(np.argmax(np.asarray(lg[0, 0])))
    assert sched.outputs()[0][0] == ref_first


def test_moe_staggered_lanes_independent():
    """The staggered-lane invariant extends to moe: dropless per-token
    dispatch means a lane's expert mix never depends on who shares the
    decode batch, so co-residents cannot perturb each other."""
    cfg = get_smoke_config("olmoe_1b_7b")
    params = lm.init_params(cfg, jax.random.key(0))
    prompts = _prompts(3, cfg.vocab, seed=35)

    def outputs_of(schedule):
        pool = KVPool.for_slots(
            cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
        )
        sched = Scheduler(cfg, params, pool, slots=SLOTS, max_len=MAX_LEN)
        for p in schedule:
            sched.submit(p, GEN)
        sched.run()
        return sched.outputs()

    together = outputs_of(prompts)  # 3 requests on 2 slots: req 2 staggers
    for i, p in enumerate(prompts):
        assert together[i] == outputs_of([p])[0], f"request {i} diverged"


def test_moe_expert_load_telemetry():
    """Serving a moe config tallies routed token-expert slots and emits
    the expert-load gauges: entropy in (0, 1], hot-expert fraction 1.0
    when no residency plan pins a subset (every expert counts as hot)."""
    from repro.runtime.tracker import MemoryTracker, replay_summary

    cfg = get_smoke_config("olmoe_1b_7b")
    params = lm.init_params(cfg, jax.random.key(0))
    pool = KVPool.for_slots(cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK)
    trk = MemoryTracker()
    sched = Scheduler(
        cfg, params, pool, slots=SLOTS, max_len=MAX_LEN, tracker=trk
    )
    for p in _prompts(2, cfg.vocab, seed=36):
        sched.submit(p, GEN)
    stats = sched.run()
    # every routed token picks top_k experts across every layer
    assert stats.expert_tokens > 0
    assert stats.expert_tokens % (cfg.experts_per_token * cfg.n_layers) == 0
    s = replay_summary(trk.records)
    assert s["expert_tokens"] == stats.expert_tokens  # replay-exact
    assert 0.0 < s["moe_expert_entropy"] <= 1.0
    assert s["moe_hot_expert_fraction"] == 1.0  # no plan -> all hot


@settings(max_examples=5, deadline=None)
@given(data=st.data())
def test_moe_dropless_routing_is_batch_independent(data):
    """Property: dropless dispatch routes each token by its own gate
    only — a row's FFN output is bit-identical whether it shares the
    batch with random co-residents or runs alone. This is the invariant
    that licensed deleting every moe serving carve-out (chunking, padded
    buckets, prefix cache, disagg all assume batch composition is
    inert)."""
    from repro.models.moe import moe_ffn_dropless

    cfg = get_smoke_config("olmoe_1b_7b")
    params = lm.init_params(cfg, jax.random.key(0))
    lp = jax.tree.map(lambda a: a[0], params["layers"])  # layer 0 weights
    seed = data.draw(st.integers(0, 2**16), label="seed")
    b = data.draw(st.sampled_from((2, 3, 4)), label="batch")
    x = jax.random.normal(jax.random.key(seed), (b, 5, cfg.d_model))

    out, counts = moe_ffn_dropless(
        x, lp["router"], lp["w1"], lp["w3"], lp["w2"], cfg
    )
    for i in range(b):
        solo, solo_counts = moe_ffn_dropless(
            x[i : i + 1], lp["router"], lp["w1"], lp["w3"], lp["w2"], cfg
        )
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(solo[0]))
    # the tally is per-token too: every token contributes exactly top_k
    assert float(counts.sum()) == b * 5 * cfg.experts_per_token


# ---------------- mid-chunk drain (ISSUE 6 regression) ----------------


def _drain_mid_chunk(cfg, params, *, budget, rounds_after_admit, slots=2,
                     max_len=64):
    """Admit one over-budget prompt (admission runs its first chunk),
    advance ``rounds_after_admit`` further rounds (one chunk each),
    drain, and return (scheduler, drained requests, the prompt)."""
    rng = np.random.default_rng(44)
    long_p = rng.integers(0, cfg.vocab, size=(24,)).astype(np.int32)
    pool = KVPool.for_slots(
        cfg, slots=slots, max_len=max_len, block_tokens=BLOCK
    )
    sched = Scheduler(
        cfg, params, pool, slots=slots, max_len=max_len, token_budget=budget
    )
    sched.submit(long_p, GEN)
    assert sched._admit_one()
    for _ in range(rounds_after_admit):
        sched.round()
    assert sched._chunk_cursor, "request must still be mid-chunked-prefill"
    return sched, sched.drain(), long_p


@pytest.mark.parametrize("rounds", [0, 1])
def test_drain_mid_chunked_prefill_leaks_nothing(rounds):
    """Regression (ISSUE 6): draining while a chunked prefill is
    in-flight must requeue the request cold — no pool blocks, no
    ``_chunk_cursor`` entry, no lane reservation left behind — at every
    chunk boundary (24-token prompt, chunk 8 -> cursors 8 and 16)."""
    cfg = _cfg()
    params = lm.init_params(cfg, jax.random.key(0))
    sched, moved, long_p = _drain_mid_chunk(
        cfg, params, budget=8, rounds_after_admit=rounds
    )
    assert [r.rid for r in moved] == [0]
    assert moved[0].state is RequestState.QUEUED
    assert moved[0].output == []
    assert not sched._chunk_cursor and not sched._chunk_lane
    assert all(slot is None for slot in sched.active)
    sched.pool.validate()
    assert sched.pool.free_blocks == sched.pool.usable_blocks
    assert sched.pool.live_requests() == []

    # the requeued request reproduces its exact single-shot stream
    # (rid-keyed sampling): resubmit on a fresh scheduler under the
    # same budget and compare against a large-budget single shot
    def serve(budget):
        pool = KVPool.for_slots(
            cfg, slots=2, max_len=64, block_tokens=BLOCK
        )
        s = Scheduler(
            cfg, params, pool, slots=2, max_len=64, token_budget=budget
        )
        s.submit(long_p, GEN, rid=moved[0].rid)
        s.run()
        return s.outputs()[moved[0].rid]

    assert serve(8) == serve(64), "post-drain replay changed the tokens"


def test_drain_mid_chunked_prefill_hybrid_releases_lane():
    """The hybrid variant additionally reserves an SSM chunk lane; the
    drain must drop it (and its carried state) with the cursor."""
    cfg = get_smoke_config("zamba2_2p7b")
    params = lm.init_params(cfg, jax.random.key(0))
    sched, moved, long_p = _drain_mid_chunk(
        cfg, params, budget=16, rounds_after_admit=0
    )
    assert [r.rid for r in moved] == [0]
    assert not sched._chunk_cursor and not sched._chunk_lane
    sched.pool.validate()
    assert sched.pool.free_blocks == sched.pool.usable_blocks

    # requeue on the same (now-drained, still-functional) scheduler:
    # identical stream to an uninterrupted chunked run
    sched.submit(long_p, GEN, rid=0)
    sched.run()
    pool2 = KVPool.for_slots(cfg, slots=2, max_len=64, block_tokens=BLOCK)
    ref = Scheduler(
        cfg, params, pool2, slots=2, max_len=64, token_budget=16
    )
    ref.submit(long_p, GEN)
    ref.run()
    assert sched.outputs()[0] == ref.outputs()[0]


def test_drain_mid_chunked_prefill_moe_leaks_nothing():
    """MoE chunked prefill drains cleanly too: no pool blocks, no chunk
    cursor, no stale expert-count accumulation from the dropped chunks —
    the requeued request replays its exact single-shot stream."""
    cfg = get_smoke_config("olmoe_1b_7b")
    params = lm.init_params(cfg, jax.random.key(0))
    sched, moved, long_p = _drain_mid_chunk(
        cfg, params, budget=8, rounds_after_admit=1
    )
    assert [r.rid for r in moved] == [0]
    assert not sched._chunk_cursor and not sched._chunk_lane
    sched.pool.validate()
    assert sched.pool.free_blocks == sched.pool.usable_blocks

    sched.submit(long_p, GEN, rid=0)
    sched.run()
    pool2 = KVPool.for_slots(cfg, slots=2, max_len=64, block_tokens=BLOCK)
    ref = Scheduler(
        cfg, params, pool2, slots=2, max_len=64, token_budget=8
    )
    ref.submit(long_p, GEN)
    ref.run()
    assert sched.outputs()[0] == ref.outputs()[0]
