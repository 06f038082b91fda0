"""The trace reduction: busy and idle time, program and kernel time, and
idle gaps named by the host span they fell in."""

import pathlib

import pytest

from bench.core import trace as tr
from bench.core.programs import split, step_programs

DATA = pathlib.Path(__file__).resolve().parent / "data"

# device operations on one chip (ns): busy 0-10, 15-25 and 40-45
OPS = [(0, 10, "fusion.1"), (5, 10, "fusion.2"), (15, 25, "weight_stream"),
       (40, 45, "fusion.1")]
HOST = [(0, 30, "bench.round"), (20, 28, "bench.on_logits"),
        (32, 50, "bench.round")]


def test_busy_is_the_union_of_operations():
    assert tr.merge(OPS, 0, 50) == [(0, 10), (15, 25), (40, 45)]
    assert tr.busy_ns(OPS, 0, 50) == 25
    assert tr.busy_ns(OPS, 8, 42) == 2 + 10 + 2


def test_idle_gaps_by_host_span():
    assert tr.idle_gaps(OPS, 0, 50) == [(10, 15), (25, 40), (45, 50)]
    # 10-15 inside a round; 25-40 has its midpoint 32.5 in the second
    # round; 45-50 in the second round
    got = tr.idle_by_host(OPS, HOST, [(0, 50)])
    assert got == {"bench.round": 5 + 15 + 5}
    # the innermost open span names a gap
    names = tr.HostSpans(HOST)
    assert names.at(26) == "bench.on_logits"
    assert names.at(12) == "bench.round"
    assert names.at(31) == "outside bench spans"
    busy = tr.merge(OPS, 0, 50)
    assert tr.gaps_between(busy, 12, 42) == [(12, 15), (25, 40)]


def test_kernel_time_and_time_by_name():
    calls = tr.matching(OPS, r"stream", 0, 50)
    assert [(s, e) for s, e, _ in calls] == [(15, 25)]
    by = tr.time_by_name(OPS, 0, 42)
    assert by == {"fusion.1": 12, "fusion.2": 5, "weight_stream": 10}


def test_decode_program_is_the_one_run_as_often_as_decode_steps():
    modules = ([(i * 10, i * 10 + 6, "jit_step#7") for i in range(16)]
               + [(200, 230, "jit_step#9"), (240, 270, "jit_step#9"),
                  (300, 301, "jit_squeeze#3")])
    groups = step_programs(modules, 0, 1000)
    assert groups == {"jit_step#7": [16, 96.0], "jit_step#9": [2, 60.0]}
    assert split(groups, 16, 2) == ("jit_step#7", ["jit_step#9"])
    assert split(groups, 0, 2) == (None, [])
    assert split({}, 5, 0) == (None, [])


@pytest.mark.parametrize("groups,decode,prefill", [
    # a chunk program that ran as often as the decode step: no guess
    ({"jit_step#7": [16, 96.0], "jit_step#9": [16, 480.0]}, 16, 16),
    # runs the profiler missed: the counts no longer fit
    ({"jit_step#7": [15, 90.0], "jit_step#9": [2, 60.0]}, 16, 2),
    ({"jit_step#7": [16, 96.0], "jit_step#9": [2, 60.0]}, 16, 3),
])
def test_split_is_unknown_where_the_counts_do_not_single_out_decode(
        groups, decode, prefill):
    assert split(groups, decode, prefill) == (None, [])


@pytest.mark.parametrize("lo,hi,want", [(0, 50, 25), (10, 15, 0)])
def test_busy_window(lo, hi, want):
    assert tr.busy_ns(OPS, lo, hi) == want


@pytest.fixture(scope="module")
def excerpt(tmp_path_factory):
    """A TPU v5e trace excerpt of the doc cell: every program run and
    benchmark annotation of 3.7 s, and 4 ms of device operations inside
    one decode step."""
    from jax.profiler import ProfileData

    text = (DATA / "tpu_trace_excerpt.pbtxt").read_text()
    path = tmp_path_factory.mktemp("trace") / "excerpt.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return tr.summarize(path)


def test_recorded_trace_programs(excerpt):
    assert excerpt.devices == [0]
    assert [n for _, _, n in excerpt.host].count("bench.round") == 1
    lo, hi = excerpt.host[0][0], max(e for _, e, _ in excerpt.host)
    groups = step_programs(excerpt.modules[0], lo, hi)
    assert sorted(g[0] for g in groups.values()) == [16, 32]
    dec, pre = split(groups, 16, 32)
    assert groups[dec][0] == 16 and len(pre) == 1
    # the decode step's device time per run, as recorded (ms)
    assert groups[dec][1] / 16 / 1e6 == pytest.approx(135.031, abs=1e-3)
    assert groups[pre[0]][1] / 32 / 1e6 == pytest.approx(46.225, abs=1e-3)


def test_recorded_trace_operations(excerpt):
    ops = excerpt.ops[0]
    loop = [o for o in ops if o[2].startswith("%while")]
    assert len(loop) == 1
    inner = tr.leaves(ops)
    assert loop[0] not in inner and len(inner) == len(ops) - 1
    s, e, _ = loop[0]
    # leaf operations cover the loop's time with gaps between them
    busy = tr.busy_ns(inner, s, e)
    assert 0 < busy <= tr.busy_ns(ops, s, e) == e - s
    assert sum(tr.time_by_name(inner, s, e).values()) >= busy
