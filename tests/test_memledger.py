"""Memory ledger (`runtime.memledger`, ISSUE 9): event-sourced
``kind="mem"`` pool-mutation records whose integrated deltas reproduce
the per-round pool gauges exactly — across drain/requeue mid-chunked
prefill, engine drain + restore churn, prefix-cache evict-to-empty and
a hypothesis refcount/COW churn sweep — plus the streaming pressure
monitor and the owner-attribution summary built on top."""

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, get_smoke_config
from repro.models import lm
from repro.runtime.cluster import FleetCluster, StepCostModel, TrafficSpec
from repro.runtime.cluster.traffic import synthesize
from repro.runtime.kv_pool import KVPool
from repro.runtime.memledger import (
    GAUGES,
    MemLedger,
    MemPolicy,
    MemPressureMonitor,
    _snapshot,
    kv_block_bytes,
    summarize_ledger,
    validate_ledger,
)
from repro.runtime.prefix_cache import PrefixCache
from repro.runtime.scheduler import Scheduler
from repro.runtime.tracker import MemoryTracker, replay_summary

BLOCK, MAX_LEN, SLOTS = 4, 32, 2


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm_360m")
    params = lm.init_params(cfg, jax.random.key(0))
    cost = StepCostModel.for_config(get_config("smollm_360m"), slots=SLOTS)
    return cfg, params, cost


def _cfg():
    return get_smoke_config("smollm_360m")


def _ledgered_pool(cfg, n_blocks=17):
    """A raw pool with an attached ledger feeding a MemoryTracker."""
    pool = KVPool(cfg, n_blocks=n_blocks, block_tokens=BLOCK)
    trk = MemoryTracker()
    clock = iter(range(10**9))
    led = MemLedger(lambda: float(next(clock)), tracker=trk)
    led.attach(pool)
    return pool, led, trk


def _integrate(mems):
    """Fold a mem-record list into absolute gauges (attach + deltas)."""
    state = None
    for r in mems:
        if r.get("op") == "attach":
            state = {k: r[k] for k in GAUGES}
        elif r.get("op") == "reserve":
            continue
        else:
            for k in GAUGES:
                state[k] += r.get("d_" + k, 0)
    return state


# ---------------- ledger unit behavior ----------------


def test_block_bytes_matches_array_footprint():
    cfg = _cfg()
    pool = KVPool(cfg, n_blocks=9, block_tokens=BLOCK)
    bb = kv_block_bytes(pool)
    assert bb * pool.k.shape[1] == pool.k.nbytes + pool.v.nbytes
    assert bb > 0


def test_attach_emits_absolute_baseline_and_binds_pool():
    cfg = _cfg()
    pool, led, trk = _ledgered_pool(cfg)
    led.flush()
    assert pool.ledger is led
    (att,) = trk.mems
    assert att["op"] == "attach" and att["owner"] == "pool"
    assert att["n_blocks"] == pool.usable_blocks
    assert att["block_tokens"] == BLOCK
    assert att["block_bytes"] == kv_block_bytes(pool)
    for k in GAUGES:
        assert att[k] == _snapshot(pool)[k]


def test_ops_emit_sparse_deltas_with_exact_bytes():
    cfg = _cfg()
    pool, led, trk = _ledgered_pool(cfg)
    bb = kv_block_bytes(pool)
    pool.admit(0, 12)
    pool.note_tokens(0, 6)
    led.sync()  # fold the note_tokens held_tokens drift
    pool.release(0)
    led.flush()
    by_op = {r["op"]: r for r in trk.mems}
    # admit: pure commitment, no blocks move
    assert by_op["admit"]["d_committed_blocks"] == 3
    assert "d_held_blocks" not in by_op["admit"]
    assert by_op["admit"]["rid"] == 0
    # grow: 6 tokens -> 2 blocks off the free list, bytes = 2 blocks
    g = by_op["grow"]
    assert g["owner"] == "request" and g["grown"] == 2
    assert g["d_held_blocks"] == 2 and g["d_free_blocks"] == -2
    assert g["d_alloc_blocks"] == 2 and g["d_bytes"] == 2 * bb
    # sync carries the un-evented held_tokens drift
    assert by_op["sync"]["d_held_tokens"] == 6
    # release returns everything
    r = by_op["release"]
    assert r["d_held_blocks"] == -2 and r["d_freed_blocks"] == 2
    assert r["d_bytes"] == -2 * bb
    # integration lands back on the live snapshot
    assert _integrate(trk.mems) == _snapshot(pool)


def test_cow_adopt_emits_shared_and_cow_deltas():
    cfg = _cfg()
    pool, led, trk = _ledgered_pool(cfg)
    pool.admit(0, 6)
    pool.note_tokens(0, 6)  # blocks [full, partial-tail]
    b_full, b_tail = pool.blocks_of(0)
    pool.admit(1, 6)
    pool.adopt_prefix(1, (b_full,), b_tail, 6)
    led.sync()
    led.flush()
    adopt = next(r for r in trk.mems if r["op"] == "adopt_prefix")
    assert adopt["shared"] == 1 and adopt["cow"] == 1
    assert adopt["d_cow_copies"] == 1
    assert adopt["d_shared_blocks"] == 1  # the full block now has 2 users
    assert adopt["d_alloc_blocks"] == 1  # the private COW duplicate
    assert _integrate(trk.mems) == _snapshot(pool)
    assert pool.cow_copies == 1


def test_reserve_records_carry_bytes_not_deltas():
    cfg = _cfg()
    pool, led, trk = _ledgered_pool(cfg)
    led.reserve("weight-resident", 1 << 20, blocks=3)
    led.reserve("ring-slot", 1 << 16, depth=2)
    led.flush()
    res = [r for r in trk.mems if r["op"] == "reserve"]
    assert [r["owner"] for r in res] == ["weight-resident", "ring-slot"]
    assert res[0]["nbytes"] == 1 << 20 and res[0]["blocks"] == 3
    assert all(not any(k.startswith("d_") for k in r) for r in res)
    # reserve records are invisible to gauge integration
    assert _integrate(trk.mems) == _snapshot(pool)
    s = summarize_ledger(trk.mems)["engines"][0]
    assert s["reserved_bytes"] == {
        "weight-resident": 1 << 20,
        "ring-slot": 1 << 16,
    }


def test_ledger_without_tracker_counts_and_drops():
    cfg = _cfg()
    pool = KVPool(cfg, n_blocks=9, block_tokens=BLOCK)
    led = MemLedger(lambda: 0.0)
    led.attach(pool)
    pool.admit(0, 8)
    pool.note_tokens(0, 8)
    pool.release(0)
    assert led.n_records == led.n_dropped >= 4
    assert led._buf == []
    # diffing kept running: a fresh sync has nothing left to fold
    n = led.n_records
    led.sync()
    assert led.n_records == n


# ---------------- bare scheduler: interleaving + exactness ----------------


def _run_sched(cfg, params, *, n=5, trk=None, **kw):
    pool = KVPool.for_slots(
        cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
    )
    trk = trk if trk is not None else MemoryTracker()
    clock = iter(range(10**9))
    led = MemLedger(lambda: float(next(clock)), tracker=trk)
    sched = Scheduler(
        cfg, params, pool, slots=SLOTS, max_len=MAX_LEN,
        prefix_cache=PrefixCache(pool), tracker=trk, ledger=led,
        mem_monitor=MemPressureMonitor(), **kw,
    )
    rng = np.random.default_rng(0)
    for _ in range(n):
        sched.submit(
            rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32), 4
        )
    stats = sched.run()
    return sched, stats, trk


@pytest.mark.parametrize("trace", [False, True])
def test_serve_builds_a_ledger_only_with_a_trace_to_write(setup, tmp_path,
                                                          trace):
    """Without ``--trace-out`` nothing reads the ledger's records, so
    ``launch.serve`` builds none and the pool snapshots nothing on each
    mutation; the pressure monitor its summary prints stays."""
    from repro.launch import serve

    cfg, params, _ = setup
    argv = ["--smoke", "--requests", "3", "--batch", str(SLOTS),
            "--max-len", str(MAX_LEN), "--block-tokens", str(BLOCK),
            "--prompt-len", "8", "--gen-len", "4"]
    if trace:
        argv += ["--trace-out", str(tmp_path / "trace.jsonl")]
    args = serve.build_parser().parse_args(argv)
    sched = serve.build_pool_engine(cfg, params, args)
    assert sched.mem_monitor is not None
    if trace:
        assert sched.ledger is not None and sched.pool.ledger is sched.ledger
    else:
        assert sched.ledger is None and sched.pool.ledger is None
    out = serve.run_pool_engine(cfg, params, args, sched)
    assert out["mem"]["observed"] == sched.stats.rounds
    assert (out["mem_records"] > 0) == trace


def test_scheduler_stream_validates_and_replays(setup):
    cfg, params, _ = setup
    sched, stats, trk = _run_sched(cfg, params)
    assert validate_ledger(trk.stream) == []
    rep = replay_summary(trk.stream)
    assert rep["completed"] == stats.completed == 5
    assert rep["generated_tokens"] == stats.generated_tokens
    # the ledger's own integration lands on the live pool
    assert _integrate(trk.mems) == _snapshot(sched.pool)
    assert sched.ledger.n_records == len(trk.mems)


def test_mem_records_flush_before_their_round_record(setup):
    """The barrier that makes the stream self-validating: every round's
    mem records land in the stream *before* the metrics record whose
    gauges they must integrate to."""
    cfg, params, _ = setup
    _, _, trk = _run_sched(cfg, params, n=3)
    seen_metrics = 0
    for r in trk.stream:
        if r["kind"] == "metrics":
            seen_metrics += 1
        elif r["kind"] == "mem" and r["op"] != "attach":
            # block motion happens inside a round: its record must not
            # trail the round's own metrics record
            pass
    # stronger: walking the stream, the integrated state at each metrics
    # record already matches — which is validate_ledger, plus the attach
    # must be the very first mem record
    mems = [r for r in trk.stream if r["kind"] == "mem"]
    assert mems[0]["op"] == "attach"
    first_metrics = next(
        i for i, r in enumerate(trk.stream) if r["kind"] == "metrics"
    )
    first_mem = next(
        i for i, r in enumerate(trk.stream) if r["kind"] == "mem"
    )
    assert first_mem < first_metrics
    assert seen_metrics > 0


def test_drain_requeue_mid_chunked_prefill_stays_exact(setup):
    """The hard seam: a drain aborts a chunked prefill mid-flight —
    partially written blocks release, the cursor drops — and the ledger
    must account for every block the abort path returns."""
    cfg, params, _ = setup
    pool = KVPool.for_slots(
        cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
    )
    trk = MemoryTracker()
    clock = iter(range(10**9))
    led = MemLedger(lambda: float(next(clock)), tracker=trk)
    sched = Scheduler(
        cfg, params, pool, slots=SLOTS, max_len=MAX_LEN,
        token_budget=16, prefill_chunk=8, tracker=trk, ledger=led,
        mem_monitor=MemPressureMonitor(),
    )
    rng = np.random.default_rng(1)
    long_prompt = rng.integers(0, cfg.vocab, size=(24,)).astype(np.int32)
    sched.submit(long_prompt, 4)
    sched.round()  # first chunk prefilled; cursor live, blocks held
    assert sched._chunk_cursor, "prompt must still be mid-chunk"
    assert pool.stats().held_blocks > 0
    moved = sched.drain()
    assert [r.rid for r in moved] == [0]
    led.sync()
    led.flush()
    assert validate_ledger(trk.stream) == []
    assert _integrate(trk.mems) == _snapshot(pool)
    assert pool.free_blocks == pool.usable_blocks  # nothing leaked
    # the abort's release is an attributed event, not silent sync drift
    assert any(
        r["op"] == "release" and r.get("rid") == 0 for r in trk.mems
    )


def test_prefix_cache_evict_to_empty_stays_exact():
    """Evicting the cache down to nothing walks uncache/evict through
    the ledger; integration must land on the all-free pool."""
    cfg = _cfg()
    pool, led, trk = _ledgered_pool(cfg, n_blocks=9)
    cache = PrefixCache(pool)
    prompt = np.arange(8, dtype=np.int32)
    pool.admit(0, 8)
    pool.note_tokens(0, 8)
    cache.commit(prompt, pool.blocks_of(0))
    led.sync()
    pool.release(0)
    st = pool.stats()
    assert st.cached_blocks == 2 and st.evictable_blocks == 2
    freed = cache.evict(100)  # far more than cached: drain to empty
    led.sync()
    led.flush()
    assert freed == 2
    st = pool.stats()
    assert st.cached_blocks == 0 and st.evictable_blocks == 0
    assert pool.free_blocks == pool.usable_blocks
    assert _integrate(trk.mems) == _snapshot(pool)
    evict = next(r for r in trk.mems if r["op"] == "evict")
    assert evict["owner"] == "prefix-cache" and evict["freed"] == 2
    # per-block frees already rode the uncache records: the evict
    # summary record itself carries no net gauge delta
    assert not any(k.startswith("d_") for k in evict)
    uncached = [r for r in trk.mems if r["op"] == "uncache"]
    assert len(uncached) == 2
    assert sum(r.get("d_freed_blocks", 0) for r in uncached) == 2


# ---------------- fleet: restore seam + surfaced summaries ----------------


def test_fleet_drain_restore_stream_stays_exact(setup):
    """Engine drain + restore churn over one shared stream: the ledger
    stays exact through the requeue storm, and the mem summaries
    surface per engine and fleet-wide."""
    cfg, params, cost = setup
    trk = MemoryTracker()
    cl = FleetCluster(
        cfg, params, n_engines=2, slots=SLOTS, max_len=MAX_LEN,
        block_tokens=BLOCK, cost=cost, policy="prefix-aware",
        prefix_cache=True, tracker=trk,
    )
    spec = TrafficSpec(
        vocab=cfg.vocab, n_requests=8, arrival_rate=2000.0,
        prompt_lens=((6, 0.5), (10, 0.5)), gen_lens=((4, 1.0),), seed=3,
    )
    res1 = cl.run(synthesize(spec), drain_at=(0, 0.0005))
    cl.restore_engine(0)
    spec2 = TrafficSpec(
        vocab=cfg.vocab, n_requests=6, arrival_rate=2000.0,
        prompt_lens=((6, 1.0),), gen_lens=((4, 1.0),), seed=4,
    )
    import dataclasses

    trace2 = [
        dataclasses.replace(r, rid=r.rid + 8) for r in synthesize(spec2)
    ]
    res2 = cl.run(trace2)
    assert len(res1.outputs) == 8 and len(res2.outputs) == 14
    assert validate_ledger(trk.stream) == []
    for e in cl.engines:
        rep = replay_summary(trk.stream, engine=e.engine_id)
        assert rep["completed"] == e.summary()["completed"]
        mem = e.summary()["mem"]
        assert mem["observed"] > 0
        assert 0.0 < mem["peak_occupancy"] <= 1.0
        assert e.summary()["fragmentation"].keys() == {
            "baseline_blocks", "ffd_blocks",
            "baseline_efficiency", "ffd_efficiency",
        }
    ms = res2.mem_summary
    assert ms["signal"] in ("ok", "pressure", "storm")
    assert ms["peak_occupancy"] > 0.0
    assert ms["headroom_blocks"] >= 0
    # both engines attached once each: exactly two attach records
    attaches = [m for m in trk.mems if m["op"] == "attach"]
    assert sorted(a["engine"] for a in attaches) == [0, 1]


def test_summarize_ledger_attributes_peaks_per_engine(setup):
    cfg, params, cost = setup
    trk = MemoryTracker()
    cl = FleetCluster(
        cfg, params, n_engines=2, slots=SLOTS, max_len=MAX_LEN,
        block_tokens=BLOCK, cost=cost, prefix_cache=True, tracker=trk,
    )
    spec = TrafficSpec(
        vocab=cfg.vocab, n_requests=6, arrival_rate=2000.0,
        prompt_lens=((8, 1.0),), gen_lens=((4, 1.0),), seed=5,
    )
    cl.run(synthesize(spec))
    s = summarize_ledger(trk.stream)
    assert [e["engine"] for e in s["engines"]] == [0, 1]
    for e in s["engines"]:
        assert e["peak_held_blocks"] > 0
        assert 0.0 < e["peak_occupancy"] <= 1.0
        assert e["alloc_blocks"] >= e["freed_blocks"] >= 0
        assert e["alloc_mib"] > 0.0
        assert e["n_records"] > 0


# ---------------- hypothesis: refcount/COW churn ----------------


@settings(max_examples=25, deadline=None)
@given(data=st.data())
def test_churn_integration_exact_every_step(data):
    """The property behind validate_ledger: after EVERY pool mutation
    (+ a sync for token drift), integrating the emitted deltas equals
    the live snapshot — admit/grow/adopt(COW)/release/cache/evict in
    random interleavings included."""
    cfg = _cfg()
    pool, led, trk = _ledgered_pool(cfg, n_blocks=17)
    cache = PrefixCache(pool)
    rng_rid = iter(range(10**6))
    live: list[int] = []
    for _ in range(data.draw(st.integers(4, 14), label="n_ops")):
        ops = ["admit"]
        if live:
            ops += ["grow", "release", "adopt"]
        if pool.cached_blocks:
            ops.append("evict")
        op = data.draw(st.sampled_from(ops), label="op")
        if op == "admit":
            total = data.draw(st.integers(2, 12), label="total")
            if pool.can_admit(total):
                rid = next(rng_rid)
                pool.admit(rid, total)
                pool.note_tokens(
                    rid, data.draw(st.integers(1, total), label="tok")
                )
                live.append(rid)
        elif op == "grow":
            rid = data.draw(st.sampled_from(live), label="rid")
            cap = pool._committed[rid] * BLOCK
            pool.note_tokens(
                rid, data.draw(st.integers(1, cap), label="grow_to")
            )
        elif op == "adopt":
            donor = data.draw(st.sampled_from(live), label="donor")
            m = pool.tokens_held(donor)
            held = pool.blocks_of(donor)
            tail = None if m % BLOCK == 0 else held[m // BLOCK]
            if pool.can_admit(m + 1):
                rid = next(rng_rid)
                pool.admit(rid, m + 1)
                pool.adopt_prefix(rid, held[: m // BLOCK], tail, m)
                live.append(rid)
        elif op == "release":
            rid = data.draw(st.sampled_from(live), label="rid")
            if data.draw(st.booleans(), label="cache_first"):
                toks = np.arange(pool.tokens_held(rid), dtype=np.int32)
                cache.commit(toks, pool.blocks_of(rid))
            live.remove(rid)
            pool.release(rid)
        elif op == "evict":
            cache.evict(data.draw(st.integers(1, 4), label="n_evict"))
        led.sync()
        led.flush()
        assert _integrate(trk.mems) == _snapshot(pool)
        pool.validate()
    led.flush()
    assert validate_ledger(trk.stream) in ([],)


# ---------------- pressure monitor ----------------


def _occupied_pool(cfg, frac):
    pool = KVPool(cfg, n_blocks=17, block_tokens=BLOCK)
    n = int(pool.usable_blocks * frac)
    if n:
        pool.admit(0, n * BLOCK)
        pool.note_tokens(0, n * BLOCK)
    return pool


def test_monitor_burn_and_pressure_signal():
    cfg = _cfg()
    mon = MemPressureMonitor(MemPolicy(max_occupancy=0.5, target=0.9))
    hot = _occupied_pool(cfg, 0.75)
    for i in range(10):
        mon.observe(t=float(i), pool=hot, evicted_blocks=0)
    # every round violated the 0.5 ceiling: burn = 1/0.1 = 10x budget
    assert mon.violations == mon.observed == 10
    assert mon.burn_rates(10.0)["60s"] == pytest.approx(10.0)
    assert mon.signal(10.0) == "pressure"
    s = mon.summary(now=10.0)
    assert s["signal"] == "pressure"
    assert s["peak_held_blocks"] == 12
    assert s["frag_at_peak"]["baseline_blocks"] == 12
    assert s["occupancy"]["n"] == 10


def test_monitor_eviction_storm_and_ok():
    cfg = _cfg()
    cool = _occupied_pool(cfg, 0.25)
    mon = MemPressureMonitor()
    for i in range(5):
        mon.observe(t=float(i), pool=cool, evicted_blocks=0)
    assert mon.signal(5.0) == "ok"
    # a cumulative eviction spike past half the pool inside the short
    # window flips the signal to storm even at low occupancy
    mon.observe(t=6.0, pool=cool, evicted_blocks=12)
    assert mon.eviction_rates(6.0)["60s"] == 12
    assert mon.signal(6.0) == "storm"
    assert mon.summary(now=6.0)["signal"] == "storm"


def test_monitor_frag_trend_flags_degradation():
    cfg = _cfg()
    mon = MemPressureMonitor(windows=(10.0, 50.0, 100.0))
    full = _occupied_pool(cfg, 0.5)  # block-aligned: utilization 1.0
    ragged = KVPool(cfg, n_blocks=17, block_tokens=BLOCK)
    for rid in range(6):
        ragged.admit(rid, 1)  # 1 token per block: utilization 1/4
        ragged.note_tokens(rid, 1)
    for i in range(40):
        mon.observe(t=float(i), pool=full)
    for i in range(40, 100):
        mon.observe(t=float(i), pool=ragged)
    trend = mon.frag_trend(100.0)
    assert trend["short_utilization"] < trend["long_utilization"]
    assert trend["degrading"]


# ---------------- validator guard rails ----------------


def test_validate_ledger_flags_missing_attach_and_drift():
    bad = [
        {"kind": "mem", "op": "grow", "owner": "request", "d_held_blocks": 1}
    ]
    errs = validate_ledger(bad)
    assert any("before attach" in e for e in errs)
    assert validate_ledger([]) == [
        "stream has no kind='mem' records (ledger never attached?)"
    ]
    # a tampered gauge is a named mismatch, not a silent pass
    cfg = _cfg()
    pool, led, trk = _ledgered_pool(cfg, n_blocks=9)
    pool.admit(0, 8)
    pool.note_tokens(0, 8)
    led.sync()
    led.flush()
    good = list(trk.stream) + [
        {
            "kind": "metrics",
            "pool_held_blocks": 99,
            "pool_utilization": 1.0,
        }
    ]
    errs = validate_ledger(good)
    assert any("pool_held_blocks=99" in e for e in errs)
