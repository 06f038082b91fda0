"""The traced run's device view: busy time over the traced window, the
operations that took most time (leaf operations: a loop's own span
holds its body's), and the idle gaps by host span."""

from __future__ import annotations

from bench.core import trace as tr

TOP = 10
# an operation's name is its whole HLO instruction; its head says enough
NAME = 120


def traced_bounds(run) -> tuple[float, float]:
    """The traced window on the trace clock: first to last benchmark
    annotation the profiler recorded."""
    host = run.trace.host
    return host[0][0], max(e for _, e, _ in host)


def round_spans(run) -> list[tuple[float, float]]:
    """The trace-clock spans of the rounds the profiler saw."""
    return [(s, e) for s, e, n in run.trace.host if n == "bench.round"]


def busy_window(run) -> tuple[float, float]:
    """(device busy seconds averaged over the chips used, traced window
    seconds)."""
    lo, hi = traced_bounds(run)
    devs = run.trace.devices
    busy = sum(tr.busy_ns(run.trace.ops[d], lo, hi) for d in devs)
    return busy / max(1, len(devs)) / 1e9, (hi - lo) / 1e9


def breakdown(run) -> dict:
    lo, hi = traced_bounds(run)
    ops: dict[str, float] = {}
    idle: dict[str, float] = {}
    devs = run.trace.devices
    for d in devs:
        for k, v in tr.time_by_name(tr.leaves(run.trace.ops[d]), lo,
                                     hi).items():
            ops[k] = ops.get(k, 0.0) + v / len(devs)
        gaps = tr.idle_by_host(run.trace.ops[d], run.trace.host, [(lo, hi)])
        for k, v in gaps.items():
            idle[k] = idle.get(k, 0.0) + v / len(devs)

    def top(d):
        items = sorted(d.items(), key=lambda kv: -kv[1])[:TOP]
        return [[name[:NAME], ns / 1e9] for name, ns in items]

    return {"device_ops": top(ops), "idle_gaps": top(idle)}
