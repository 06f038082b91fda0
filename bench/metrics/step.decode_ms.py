"""Device time of one run of the decode step, from the profiler trace:
the decode program's device time over its runs while the profiler was
on (``bench.core.programs`` tells it from the prefill programs)."""

from bench.core.breakdown import traced_bounds
from bench.core.programs import split, step_programs


def read(run):
    if run.trace is None or run.traced_counts is None or not run.trace.host:
        return None
    if not run.trace.devices:
        return None
    lo, hi = traced_bounds(run)
    groups = step_programs(run.trace.modules.get(run.trace.devices[0], ()),
                           lo, hi)
    dec, _ = split(groups, run.traced_delta("decode_steps"),
                   run.traced_delta("prefill_steps"))
    if dec is None:
        return None
    runs, ns = groups[dec]
    return ns / runs / 1e6
