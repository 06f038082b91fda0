"""Share of the rounds' wall time in which no operation ran on the
device, within the benchmark's ``bench.round`` annotations around
``Scheduler.round`` calls (every round the benchmark runs has work)."""

from bench.core import trace as tr
from bench.core.breakdown import round_spans


def read(run):
    if run.trace is None:
        return None
    spans = round_spans(run)
    wall = sum(e - s for s, e in spans)
    if not wall or not run.trace.devices:
        return None
    devs = run.trace.devices
    lo, hi = spans[0][0], max(e for _, e in spans)
    idle = 0.0
    for d in devs:
        busy = tr.merge(run.trace.ops[d], lo, hi)
        idle += sum(
            e - s for a, b in spans for s, e in tr.gaps_between(busy, a, b)
        )
    idle /= len(devs)
    return 100.0 * idle / wall
