"""Request-lifecycle span tracing (`runtime.spans`, ISSUE 8): exact
latency decomposition (every completed request's phase spans tile
[submit, done] with float-equal chaining), the Perfetto trace_event
export, and streaming SLO burn-rate monitoring — plus a hypothesis
sweep asserting the decomposition invariant over random fleets."""

import json
import math
import time

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import get_config, get_smoke_config
from repro.models import lm
from repro.perf.trace_export import to_trace_events, validate_trace_events
from repro.runtime.cluster import (
    DisaggCluster,
    FleetCluster,
    SloPolicy,
    StepCostModel,
    TrafficSpec,
)
from repro.runtime.cluster.traffic import ClientRequest, synthesize
from repro.runtime.kv_pool import KVPool
from repro.runtime.scheduler import Scheduler
from repro.runtime.spans import (
    SLOMonitor,
    SpanRecorder,
    StreamingHist,
    VirtualClock,
    decompose,
    request_events,
    request_spans,
    validate_trace,
)
from repro.runtime.tracker import JsonlTracker, MemoryTracker, read_jsonl

SLOTS, MAX_LEN, BLOCK = 2, 48, 4


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("smollm_360m")
    params = lm.init_params(cfg, jax.random.key(0))
    cost = StepCostModel.for_config(get_config("smollm_360m"), slots=SLOTS)
    return cfg, params, cost


def _stream(mem: MemoryTracker) -> list[dict]:
    """One mixed record list, the shape a JSONL file replays to."""
    return mem.records + mem.spans


def _run_fleet(cfg, params, cost, *, n_requests=10, seed=3, slo=None,
               arrival_rate=2000.0, drain_at=None, tracker=None):
    mem = tracker if tracker is not None else MemoryTracker()
    cl = FleetCluster(
        cfg, params, n_engines=2, slots=SLOTS, max_len=MAX_LEN,
        block_tokens=BLOCK, cost=cost, policy="prefix-aware",
        prefix_cache=True, tracker=mem, slo=slo,
    )
    spec = TrafficSpec(
        vocab=cfg.vocab, n_requests=n_requests, arrival_rate=arrival_rate,
        prompt_lens=((6, 0.5), (10, 0.5)), gen_lens=((4, 1.0),), seed=seed,
    )
    res = cl.run(synthesize(spec), drain_at=drain_at)
    return cl, res, mem


# ---------------- recorder unit behavior ----------------


def test_recorder_tiles_gaps_and_chains_exactly():
    clock = VirtualClock()
    mem = MemoryTracker()
    rec = SpanRecorder(clock.now, tracker=mem, engine=0, role="both")
    rec.open(7, "queue", t0=0.0)
    clock.advance(0.1 + 1.23e-13)  # sub-ns dust must round away
    t_admit = rec.close(7)
    assert t_admit == round(t_admit, 9)
    # a gap before the next phase is tiled with an explicit wait span
    rec.mark(7, "prefill", t_admit + 0.05, t_admit + 0.06, tokens=8)
    rec.flush()
    spans = mem.spans
    assert [s["phase"] for s in spans] == ["queue", "wait", "prefill"]
    for a, b in zip(spans, spans[1:]):
        assert b["t0"] == a["t1"]  # float-equal chaining, no tolerance
    assert spans[0]["engine"] == 0 and spans[0]["role"] == "both"
    assert spans[2]["tokens"] == 8
    assert rec.n_spans == 3 and rec._buf == []


def test_recorder_abort_marks_and_request_spans_drops_the_visit():
    clock = VirtualClock()
    mem = MemoryTracker()
    rec = SpanRecorder(clock.now, tracker=mem, engine=0)
    rec.open(1, "queue", t0=0.0)
    clock.advance(0.5)
    rec.abort(1, reason="drain")
    rec2 = SpanRecorder(clock.now, tracker=mem, engine=1)
    rec2.open(1, "queue", t0=0.0)  # requeued: clock restarts at arrival
    clock.advance(0.1)
    rec2.close(1)
    rec.flush(), rec2.flush()
    aborted = [s for s in mem.spans if s.get("aborted")]
    assert len(aborted) == 1 and aborted[0]["reason"] == "drain"
    surv = request_spans(mem.spans)
    assert [s["engine"] for s in surv[1]] == [1]  # visit 0 excluded whole


def test_recorder_without_tracker_keeps_no_buffer():
    clock = VirtualClock()
    rec = SpanRecorder(clock.now, tracker=None)
    for i in range(100):
        rec.mark(0, "prefill", float(i), float(i) + 1.0)
    assert rec.n_spans == 100 and rec._buf == []
    rec.flush()  # no tracker: must not raise


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter
    and exit by name."""

    def __init__(self):
        self.log: list[str] = []

    def __call__(self, name):
        log = self.log

        class _Ann:
            def __enter__(self):
                log.append(name)

            def __exit__(self, *exc):
                log.append("/" + name)

        return _Ann()


@pytest.mark.parametrize("tracked", [True, False])
def test_round_phase_opens_its_annotation_and_records_when_tracked(
        monkeypatch, tracked):
    from repro.runtime import spans as spans_mod

    ann = _Annotations()
    monkeypatch.setattr(spans_mod, "TraceAnnotation", ann)
    clock = VirtualClock()
    mem = MemoryTracker()
    rec = SpanRecorder(clock.now, tracker=mem if tracked else None,
                       engine=2, role="both")
    with rec.phase("admit", round=4):
        clock.advance(0.25)
    rec.flush()
    assert ann.log == ["serve.admit", "/serve.admit"]
    want = [{"kind": "span", "phase": "round.admit", "engine": 2,
             "role": "both", "t0": 0.0, "t1": 0.25, "round": 4}]
    assert mem.spans == (want if tracked else [])
    # a phase is no request span: nothing tiles, nothing is counted
    assert rec.n_spans == 0 and rec._buf == [] and rec._last == {}


def test_request_readers_skip_round_phases_and_scope_tables(setup):
    """A fleet stream with round phases and the decode program's scope
    tables decomposes exactly as its request spans alone do."""
    cfg, params, cost = setup
    _, _, mem = _run_fleet(cfg, params, cost, n_requests=6, seed=5)
    recs = _stream(mem)
    laneless = [r for r in recs if r.get("kind") == "span" and "rid" not in r]
    phases = {r["phase"] for r in laneless}
    assert {"round.admit", "round.decode_dispatch", "round.logits_fetch",
            "round.sample", "round.round_tail", "step.scopes"} <= phases
    bare = [r for r in recs if r not in laneless]
    assert validate_trace(recs) == [] == validate_trace(bare)
    assert request_spans(recs) == request_spans(bare)
    assert decompose(recs) == decompose(bare)
    doc = to_trace_events(recs)
    assert validate_trace_events(doc) == []
    assert doc == to_trace_events(bare)


# ---------------- standalone scheduler, wall clock ----------------


def test_standalone_scheduler_wall_clock_spans(setup):
    """A bare Scheduler with a monotonic-clock recorder emits tiled
    spans through the same tracker stream (the `launch.serve
    --trace-out` path)."""
    cfg, params, _ = setup
    rng = np.random.default_rng(0)
    mem = MemoryTracker()
    pool = KVPool.for_slots(
        cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
    )
    rec = SpanRecorder(time.monotonic, tracker=mem)
    sched = Scheduler(
        cfg, params, pool, slots=SLOTS, max_len=MAX_LEN,
        tracker=mem, spans=rec,
    )
    for _ in range(3):
        sched.submit(
            rng.integers(0, cfg.vocab, size=(8,)).astype(np.int32), 4
        )
    sched.run()
    assert rec.n_spans > 0 and mem.spans
    assert {"queue", "prefill", "decode"} <= {s["phase"] for s in mem.spans}
    groups = request_spans(mem.spans)
    assert set(groups) == {0, 1, 2}
    for spans in groups.values():  # contiguity holds on the wall clock too
        for a, b in zip(spans, spans[1:]):
            assert b["t0"] == a["t1"]


def test_scheduler_records_its_round_phases(setup):
    """A tracked scheduler records one admission and one tail per round,
    and per decode step its dispatch, logits fetch and sampling, in that
    order and without overlap, each tagged with its round."""
    cfg, params, _ = setup
    rng = np.random.default_rng(2)
    mem = MemoryTracker()
    pool = KVPool.for_slots(
        cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
    )
    sched = Scheduler(
        cfg, params, pool, slots=SLOTS, max_len=MAX_LEN, token_budget=16,
        spans=SpanRecorder(time.monotonic, tracker=mem),
    )
    sched.submit(rng.integers(0, cfg.vocab, size=(24,)).astype(np.int32), 3)
    sched.submit(rng.integers(0, cfg.vocab, size=(6,)).astype(np.int32), 4)
    stats = sched.run()
    phases = [s for s in mem.spans if s["phase"].startswith("round.")]
    assert all("rid" not in s for s in phases)
    count = lambda name: sum(s["phase"] == f"round.{name}" for s in phases)
    assert count("admit") == count("round_tail") == stats.rounds
    for name in ("decode_dispatch", "logits_fetch", "sample"):
        assert count(name) == stats.decode_steps
    assert count("prefill_chunk") >= 2  # the 24-token prompt, chunked
    steps = [s for s in phases if s["phase"] in (
        "round.decode_dispatch", "round.logits_fetch", "round.sample")]
    for a, b in zip(steps, steps[1:]):
        assert a["t1"] <= b["t0"] and a["round"] <= b["round"]
    assert [s["phase"] for s in steps[:3]] == [
        "round.decode_dispatch", "round.logits_fetch", "round.sample"]


def test_untraced_scheduler_opens_only_annotations(setup, monkeypatch):
    """With no recorder the round phases still reach the profiler."""
    from repro.runtime import spans as spans_mod

    ann = _Annotations()
    monkeypatch.setattr(spans_mod, "TraceAnnotation", ann)
    cfg, params, _ = setup
    rng = np.random.default_rng(3)
    pool = KVPool.for_slots(
        cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
    )
    sched = Scheduler(cfg, params, pool, slots=SLOTS, max_len=MAX_LEN,
                      token_budget=16)
    sched.submit(rng.integers(0, cfg.vocab, size=(24,)).astype(np.int32), 3)
    sched.run()
    opened = {n for n in ann.log if not n.startswith("/")}
    assert opened == {f"serve.{p}" for p in (
        "admit", "prefill_chunk", "decode_dispatch", "logits_fetch", "sample",
        "round_tail")}
    assert ann.log.count("serve.admit") == sched.stats.rounds


def test_attaching_a_tracked_recorder_records_the_decode_scopes(setup):
    """Attached to a warm scheduler (as a traced serving run attaches it
    after warm-up), a tracked recorder gets the decode program's scope
    table at once, and nothing compiles for it."""
    cfg, params, _ = setup
    rng = np.random.default_rng(6)
    pool = KVPool.for_slots(
        cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
    )
    sched = Scheduler(cfg, params, pool, slots=SLOTS, max_len=MAX_LEN)
    sched.submit(rng.integers(0, cfg.vocab, size=(6,)).astype(np.int32), 3)
    sched.run()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda key, _s, **_kw: compiles.append(key)
    )
    mem = MemoryTracker()
    sched.spans = SpanRecorder(time.monotonic, tracker=mem)
    assert "/jax/core/compile/backend_compile_duration" not in compiles
    (rec,) = mem.spans
    assert rec["phase"] == "step.scopes" and rec["program"] == "decode"
    assert {"attention/kv_gather", "attention/kv_write", "ffn",
            "logits"} <= set(rec["scopes"].values())
    # an untracked recorder gets no table
    sched.spans = SpanRecorder(time.monotonic)
    assert len(mem.spans) == 1


def test_scheduler_drain_aborts_open_timelines(setup):
    cfg, params, _ = setup
    rng = np.random.default_rng(4)
    clock = VirtualClock()
    mem = MemoryTracker()
    rec = SpanRecorder(clock.now, tracker=mem)
    pool = KVPool.for_slots(
        cfg, slots=SLOTS, max_len=MAX_LEN, block_tokens=BLOCK
    )
    sched = Scheduler(
        cfg, params, pool, slots=SLOTS, max_len=MAX_LEN,
        token_budget=8, tracker=mem, spans=rec,
    )
    long_p = rng.integers(0, cfg.vocab, size=(24,)).astype(np.int32)
    sched.submit(long_p, 4)
    sched._admit_one()  # first chunk in, request mid-flight
    moved = sched.drain()
    rec.flush()
    assert [r.rid for r in moved] == [0]
    assert any(s.get("aborted") for s in mem.spans)
    assert request_spans(mem.spans) == {}  # the whole visit is excluded


# ---------------- fleet decomposition exactness ----------------


def test_fleet_trace_decomposes_exactly(setup):
    """The tentpole invariant: every completed request's spans tile
    [submit, done] with float-equal chaining, milestone stamps land on
    span boundaries, and pre-first phase durations telescope to exactly
    the submit-relative TTFT."""
    cfg, params, cost = setup
    cl, res, mem = _run_fleet(cfg, params, cost, n_requests=10, seed=3)
    recs = _stream(mem)
    assert validate_trace(recs) == []
    events = request_events(recs)
    spans_by = request_spans(recs)
    assert set(events) == set(res.outputs) == set(spans_by)
    for rid, timing in res.timings.items():
        ev = events[rid]
        assert ev["first"] == pytest.approx(timing.t_first, abs=1e-9)
        assert ev["done"] == pytest.approx(timing.t_done, abs=1e-9)
        assert ev["admit"] == pytest.approx(timing.t_admit, abs=1e-9)
        first_span = spans_by[rid][0]
        assert first_span["phase"] == "queue"
        assert first_span["t0"] == pytest.approx(
            timing.t_arrival, abs=1e-9
        )
        # TTFT decomposition: pre-first phases sum to the client TTFT
        pre = math.fsum(
            s["t1"] - s["t0"]
            for s in spans_by[rid]
            if s["t1"] <= ev["first"]
        )
        assert pre == pytest.approx(timing.ttft, abs=1e-9)
    # phase totals cover [submit, done] for every request
    for rid, agg in decompose(recs).items():
        total = math.fsum(agg.values())
        t0 = spans_by[rid][0]["t0"]
        assert abs(total - (events[rid]["done"] - t0)) < 1e-9


def test_ttft_submit_vs_admit_split(setup):
    """Satellite 1: TTFT is measured from submission; the spread to the
    admission-relative reading is exactly the queue wait."""
    cfg, params, cost = setup
    _, res, _ = _run_fleet(
        cfg, params, cost, n_requests=12, seed=9, arrival_rate=5000.0
    )
    rep = res.report(SloPolicy(ttft=10.0, tpot=10.0))
    for t in res.timings.values():
        assert not math.isnan(t.t_admit)
        assert t.queue_wait >= -1e-12  # admission never precedes arrival
        assert t.ttft == pytest.approx(
            t.queue_wait + t.ttft_admit, abs=1e-9
        )
    assert rep.ttft_p95 >= rep.ttft_admit_p95 - 1e-12
    assert rep.queue_wait_p95 >= 0.0
    assert rep.ttft_admit_p95 > 0.0


def test_fleet_drain_requeue_timeline_still_tiles(setup):
    """Requests drained mid-flight restart elsewhere; their aborted
    engine-visits are excluded and the surviving timeline still tiles
    [submit, done] exactly."""
    cfg, params, cost = setup
    rng = np.random.default_rng(11)
    mem = MemoryTracker()
    cl = FleetCluster(
        cfg, params, n_engines=2, slots=SLOTS, max_len=MAX_LEN,
        block_tokens=BLOCK, cost=cost, policy="prefix-aware",
        prefix_cache=True, tracker=mem,
    )
    fresh = lambda k: rng.integers(0, cfg.vocab, size=(k,)).astype(np.int32)
    burst = [
        ClientRequest(i, 0.001 * i, fresh(int(rng.integers(8, 15))),
                      int(rng.choice((4, 8))), i)
        for i in range(8)
    ]
    res = cl.run(burst, drain_at=(0, 0.0035))
    cl.restore_engine(0)
    assert len(res.outputs) == len(burst)
    recs = _stream(mem)
    assert validate_trace(recs) == []
    aborted = [s for s in mem.spans if s.get("aborted")]
    if aborted:  # the drain actually moved someone
        surv = request_spans(recs)
        for s in aborted:
            assert all(
                x["engine"] != s["engine"] for x in surv.get(s["rid"], [])
            )


def test_disagg_handoff_span_and_transit(setup):
    """Disagg: the handoff span carries the virtual interconnect transit
    (tokens * handoff_s_per_token), the decode-side timeline continues
    at the payload's ready time, and the whole trace still decomposes."""
    cfg, params, cost = setup
    mem = MemoryTracker()
    spec = TrafficSpec(
        vocab=cfg.vocab, n_requests=6, arrival_rate=2000.0,
        prompt_lens=((8, 1.0),), gen_lens=((4, 1.0),), seed=7,
    )
    cl = DisaggCluster(
        cfg, params, n_engines=3, slots=SLOTS, max_len=MAX_LEN,
        block_tokens=BLOCK, cost=cost, spec=spec, tracker=mem,
    )
    res = cl.run(synthesize(spec))
    recs = _stream(mem)
    assert validate_trace(recs) == []
    hand = [s for s in mem.spans if s["phase"] == "handoff"]
    assert len(hand) == len(res.outputs)
    for s in hand:
        assert s["role"] == "prefill"
        assert s["t1"] - s["t0"] == pytest.approx(
            s["tokens"] * cost.handoff_s_per_token, abs=1e-9
        )
    for rid, spans in request_spans(recs).items():
        roles = [s["role"] for s in spans]
        assert roles[0] == "prefill" and "decode" in roles


@settings(max_examples=5, deadline=None)
@given(data=st.data())
def test_random_fleets_decompose_exactly(setup, data):
    """Property: random seeds, loads, and fleet shapes never break the
    exact-decomposition invariant (the span analogue of the tracker's
    replay-conservation sweep)."""
    cfg, params, cost = setup
    seed = data.draw(st.integers(0, 2**16), label="seed")
    n_req = data.draw(st.sampled_from((4, 8, 12)), label="n_req")
    rate = data.draw(st.sampled_from((100.0, 2000.0)), label="rate")
    cl, res, mem = _run_fleet(
        cfg, params, cost, n_requests=n_req, seed=seed, arrival_rate=rate
    )
    assert len(res.outputs) == n_req
    assert validate_trace(_stream(mem)) == [], seed


# ---------------- SLO monitoring ----------------


def test_slo_monitor_burn_rates():
    mon = SLOMonitor(
        SloPolicy(ttft=0.1, tpot=0.01, target=0.9), windows=(10.0, 100.0)
    )
    for i in range(20):
        mon.observe(t=float(i), ttft=0.05, tpot=0.005, queue_wait=0.01)
    for i in range(5):
        mon.observe(t=20.0 + i, ttft=1.0, tpot=0.005)  # TTFT violations
    s = mon.summary(now=25.0)
    assert s["observed"] == 25 and s["violations"] == 5
    # last 10s: 5 ok + 5 bad -> rate .5 / budget .1; 100s: 5/25 / .1
    assert s["burn_10s"] == pytest.approx(5.0)
    assert s["burn_100s"] == pytest.approx(2.0)
    assert s["queue_wait"]["n"] == 20  # nan milestones don't count
    assert s["ttft"]["max"] == 1.0
    assert s["ttft"]["p50"] <= s["ttft"]["p99"] <= s["ttft"]["max"]


def test_slo_monitor_without_policy_streams_hists_only():
    mon = SLOMonitor()
    mon.observe(t=0.0, ttft=0.2, tpot=0.001)
    s = mon.summary(now=1.0)
    assert s["observed"] == 1 and "violations" not in s
    assert mon.burn_rates(1.0) == {}


def test_streaming_hist_percentiles_bracket_exact():
    rng = np.random.default_rng(0)
    xs = rng.lognormal(mean=-3.0, sigma=1.0, size=2000)
    h = StreamingHist()
    for x in xs:
        h.add(float(x))
    for q in (50, 95, 99):
        exact = float(np.percentile(xs, q))
        est = h.percentile(q)
        # log buckets at 8/decade: within one bucket ratio (10^(1/8))
        assert exact * 0.9 <= est <= exact * 1.4, (q, exact, est)
    assert h.percentile(100) == float(xs.max())


def test_fleet_surfaces_slo_and_burn_rates(setup):
    cfg, params, cost = setup
    slo = SloPolicy(ttft=10.0, tpot=10.0, target=0.99)
    cl, res, _ = _run_fleet(cfg, params, cost, n_requests=8, slo=slo)
    assert res.slo_summary["observed"] == len(res.outputs)
    assert res.slo_summary["violations"] == 0
    assert any(k.startswith("burn_") for k in res.slo_summary)
    per_engine = [e.summary() for e in cl.engines]
    assert sum(s["slo"]["observed"] for s in per_engine) == len(res.outputs)
    assert all(s["spans"] > 0 for s in per_engine)
    assert all(s["slo"]["queue_wait"]["n"] == s["completed"]
               for s in per_engine)


# ---------------- Perfetto export ----------------


def test_trace_export_roundtrip_and_flows(setup, tmp_path):
    """A real disagg trace exports to valid trace_event JSON with one
    named track per engine and paired handoff flow arrows."""
    cfg, params, cost = setup
    path = tmp_path / "trace.jsonl"
    tracker = JsonlTracker(path)
    spec = TrafficSpec(
        vocab=cfg.vocab, n_requests=6, arrival_rate=2000.0,
        prompt_lens=((8, 1.0),), gen_lens=((4, 1.0),), seed=7,
    )
    cl = DisaggCluster(
        cfg, params, n_engines=3, slots=SLOTS, max_len=MAX_LEN,
        block_tokens=BLOCK, cost=cost, spec=spec, tracker=tracker,
    )
    res = cl.run(synthesize(spec))
    tracker.finish()

    from repro.perf import trace_export

    out = tmp_path / "trace.perfetto.json"
    assert trace_export.main([str(path), "--check", "-o", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert validate_trace_events(doc) == []
    evs = doc["traceEvents"]
    track_names = {
        e["args"]["name"] for e in evs if e["ph"] == "M"
    }
    assert any("prefill" in n for n in track_names)
    assert any("decode" in n for n in track_names)
    starts = [e for e in evs if e["ph"] == "s"]
    # every request crossed prefill -> decode exactly once
    assert len(starts) == len(res.outputs)
    assert all(e["cat"] == "handoff" for e in starts)
    assert any(e["ph"] == "C" for e in evs)  # gauges became counters


def test_validate_trace_events_catches_malformed():
    assert validate_trace_events({}) != []
    assert validate_trace_events({"traceEvents": {}}) != []
    bad_dur = {"traceEvents": [{"ph": "X", "name": "x", "ts": 0.0}]}
    assert any("dur" in e for e in validate_trace_events(bad_dur))
    bad_ts = {"traceEvents": [{"ph": "C", "name": "c"}]}
    assert any("ts" in e for e in validate_trace_events(bad_ts))
    unpaired = {
        "traceEvents": [{"ph": "s", "name": "f", "ts": 0.0, "id": 1}]
    }
    assert any("unpaired" in e for e in validate_trace_events(unpaired))
    ok = to_trace_events([])
    assert validate_trace_events(ok) == []
