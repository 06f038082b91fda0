"""The readers of the program's round phases and step scopes, on
synthetic runs: the decode step's KV sub-layer time from a scope table
and a device trace, the host turnaround between decode steps, and the
admission time per round, with the cases in which each stays silent."""

import pathlib

import pytest

from bench.core import registry
from bench.core.record import Run
from bench.core.trace import TraceSummary
from bench.core.window import Window

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"


def reader(name):
    return registry.reader(ROOT, "metrics", name)


def make_run(spans=None, trace=None, counts=(16, 2), t_open=0.0,
             t_close=100.0):
    window = Window(seconds=t_close - t_open, t_open=t_open,
                    t_close=t_close)
    traced_counts = None
    if trace is not None:
        decode, prefill = counts
        traced_counts = ({"decode_steps": 0, "prefill_steps": 0},
                         {"decode_steps": decode, "prefill_steps": prefill})
    return Run(cell="c", cfg=None, workload=None, window=window, setup_s=0.0,
               peaks=None, spans=spans, trace=trace,
               traced=(t_open, t_close) if trace is not None else None,
               traced_counts=traced_counts)


# ---------------- step.decode_kv_ms ----------------

TABLE = {"fusion.1": "attention/kv_gather", "fusion.2": "attention/kv_write",
         "fusion.3": "attention", "fusion.4": "ffn", "fusion.5": "logits"}


def decode_trace(decode_ops, runs=4):
    """``runs`` decode programs of 100 ns at 1000 ns apart, each a loop
    over ``decode_ops`` ((offset, length, instruction) triples, the loop
    starting 1 ns before its body), and two runs of a prefill program
    whose operations the reader must not count."""
    modules, ops = [], []
    for i in range(runs):
        t = 1000 * i
        modules.append((t, t + 100, "jit_step#7"))
        ops.append((t, t + 100, "%while.2 = (s32[]) while(...)"))
        ops += [(t + 1 + o, t + 1 + o + n, f"%{name} = bf16[8] fusion(...)")
                for o, n, name in decode_ops]
    for t in (500, 1500):
        modules.append((t, t + 200, "jit_step#9"))
        ops.append((t, t + 200, "%fusion.1 = bf16[8] fusion(...)"))
    host = [(0, 1000 * runs, "bench.round")]
    return TraceSummary(ops={0: sorted(ops)}, modules={0: sorted(modules)},
                        host=host)


def scopes_record(table=TABLE, program="decode"):
    return {"kind": "span", "phase": "step.scopes", "program": program,
            "scopes": table}


def test_kv_time_is_the_scoped_kv_operations_per_decode_run():
    # per run: 30 ns gather, 10 ns write, 40 ns elsewhere in scope, and a
    # 10 ns copy no scope holds
    ops = [(0, 30, "fusion.1"), (30, 10, "fusion.2"), (40, 20, "fusion.3"),
           (60, 10, "fusion.4"), (70, 10, "fusion.5"), (80, 9, "copy.9")]
    run = make_run([scopes_record()], decode_trace(ops), counts=(4, 2))
    assert reader("step.decode_kv_ms").read(run) == pytest.approx(40e-6)


def test_kv_time_is_silent_when_scoped_operations_cover_under_half():
    ops = [(0, 30, "fusion.1"), (30, 60, "copy.9")]  # 30 of 100 ns scoped
    run = make_run([scopes_record()], decode_trace(ops), counts=(4, 2))
    assert reader("step.decode_kv_ms").read(run) is None


@pytest.mark.parametrize("spans,counts", [
    ([], (4, 2)),  # the program records no scope table (older program)
    ([scopes_record(program="verify")], (4, 2)),
    ([scopes_record()], (4, 3)),  # the counts do not single out decode
])
def test_kv_time_is_silent_without_table_or_decode_program(spans, counts):
    ops = [(0, 99, "fusion.1")]
    run = make_run(spans, decode_trace(ops), counts=counts)
    assert reader("step.decode_kv_ms").read(run) is None


def test_kv_time_is_silent_without_a_trace():
    assert reader("step.decode_kv_ms").read(make_run([scopes_record()])) is None


def test_kv_time_on_a_recorded_tpu_trace(tmp_path):
    """The recorded doc trace holds the device operations of 4 ms of one
    decode step: a scope table naming the pool's gathers covers far less
    than half of the decode program's time, so the reader stays silent;
    the same operations do match the table by their instruction names."""
    from jax.profiler import ProfileData

    from bench.core import trace as tr

    text = (DATA / "tpu_trace_excerpt.pbtxt").read_text()
    path = tmp_path / "excerpt.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    summary = tr.summarize(path)
    table = {"fusion.150": "attention/kv_gather",
             "fusion.153": "attention/kv_gather"}
    run = make_run([scopes_record(table)], summary, counts=(16, 32))
    mod = reader("step.decode_kv_ms")
    assert mod.read(run) is None
    names = {mod.INSTRUCTION.match(n).group(1) for _, _, n in summary.ops[0]}
    assert set(table) <= names


# ---------------- host.step_turnaround_ms_p50 ----------------


def phase(name, t0, t1, rnd):
    return {"kind": "span", "phase": f"round.{name}", "t0": t0, "t1": t1,
            "round": rnd}


def steps(rnd, t, n, fetch_end, gap):
    """``n`` decode steps of one round from ``t``: dispatch 1 ms, device
    until ``fetch_end`` after the dispatch, then ``gap`` ms to the next."""
    out = []
    for _ in range(n):
        out.append(phase("decode_dispatch", t, t + 0.001, rnd))
        out.append(phase("logits_fetch", t + 0.001, t + fetch_end, rnd))
        out.append(phase("sample", t + fetch_end, t + fetch_end + gap, rnd))
        t += fetch_end + gap
    return out


def test_turnaround_pairs_each_fetch_with_the_next_dispatch_of_its_round():
    # round 0: fetch ends, 2 ms of sampling, then a 1 ms dispatch: 3 ms;
    # round 1: 4 ms; the pair across the two rounds does not count
    spans = steps(0, 0.0, 3, 0.1, 0.002) + steps(1, 1.0, 4, 0.1, 0.003)
    spans.append({"kind": "span", "rid": 0, "phase": "queue", "t0": 0.0,
                  "t1": 0.5})
    run = make_run(spans)
    got = reader("host.step_turnaround_ms_p50").read(run)
    # 2 turns of 3 ms and 3 of 4 ms: the median is 4 ms
    assert got == pytest.approx(4.0)


def test_turnaround_counts_dispatches_in_the_window_only():
    spans = steps(0, 0.0, 3, 0.1, 0.002) + steps(1, 10.0, 3, 0.1, 0.005)
    run = make_run(spans, t_open=5.0, t_close=20.0)
    assert reader("host.step_turnaround_ms_p50").read(run) == pytest.approx(
        6.0)


@pytest.mark.parametrize("spans", [
    None,  # an untraced run
    [],  # a program without round phases
    steps(0, 0.0, 1, 0.1, 0.002) + steps(1, 1.0, 1, 0.1, 0.002),
])
def test_turnaround_is_silent_without_consecutive_steps(spans):
    assert reader("host.step_turnaround_ms_p50").read(make_run(spans)) is None


# ---------------- sched.admit_ms_per_round ----------------


def test_admission_time_per_round_of_the_window():
    spans = [phase("admit", 1.0, 1.010, 0), phase("admit", 2.0, 2.0, 1),
             phase("admit", 3.0, 3.050, 2), phase("admit", 30.0, 31.0, 3),
             phase("prefill_chunk", 2.0, 2.5, 1)]
    run = make_run(spans, t_open=0.5, t_close=10.0)
    assert reader("sched.admit_ms_per_round").read(run) == pytest.approx(20.0)


@pytest.mark.parametrize("spans", [None, [phase("admit", 30.0, 31.0, 0)]])
def test_admission_time_is_silent_without_rounds_in_the_window(spans):
    run = make_run(spans, t_close=10.0)
    assert reader("sched.admit_ms_per_round").read(run) is None


# ---------------- the readers the phases sit beside ----------------


def test_request_span_readers_ignore_round_phases():
    """``spans_of`` keeps handing the queue and prefill readers exactly
    the request spans they read before the program recorded phases."""
    request = [
        {"kind": "span", "rid": 1, "phase": "queue", "t0": 0.0, "t1": 1.0},
        {"kind": "span", "rid": 1, "phase": "prefill", "t0": 1.0,
         "t1": 2.0, "tokens": 8},
    ]
    mixed = (request[:1] + steps(0, 1.0, 2, 0.1, 0.002)
             + [scopes_record()] + request[1:]
             + [phase("admit", 1.0, 2.0, 0), phase("prefill_chunk", 1, 2, 0)])
    before, after = make_run(request), make_run(mixed)
    for name in ("queue", "prefill"):
        assert after.spans_of(name) == before.spans_of(name)


def test_traced_rehearsal_reads_the_program_phases():
    """The program and the readers agree on the records' names: a traced
    CPU run reports both host-side phase metrics, and no device number."""
    from bench.run import run_cell

    res = run_cell(ROOT, "smollm_360m.chat", 12, 0.5, True, rehearsal=True,
                   strict=False)
    assert res["metrics"]["host.step_turnaround_ms_p50"]["value"] > 0
    assert res["metrics"]["sched.admit_ms_per_round"]["value"] > 0
    assert "step.decode_kv_ms" not in res["metrics"]
