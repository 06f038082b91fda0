"""Device time of the prefill programs (256-token chunks and single-step
buckets) per 1000 prompt tokens they computed, while the profiler was
on. Tokens are the program's ``prefill_tokens`` counter, which counts
the prompt tokens prefilled and not the ones served from the cache."""

from bench.core.breakdown import traced_bounds
from bench.core.programs import split, step_programs


def read(run):
    if run.trace is None or run.traced_counts is None or not run.trace.host:
        return None
    if not run.trace.devices:
        return None
    tokens = run.traced_delta("prefill_tokens")
    lo, hi = traced_bounds(run)
    groups = step_programs(run.trace.modules.get(run.trace.devices[0], ()),
                           lo, hi)
    _, prefill = split(groups, run.traced_delta("decode_steps"),
                   run.traced_delta("prefill_steps"))
    if not tokens or not prefill:
        return None
    ns = sum(groups[k][1] for k in prefill)
    return ns / 1e6 / (tokens / 1000)
