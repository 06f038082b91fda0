"""Device time of the decode step's KV sub-layer, per run of the decode
program (``bench.core.programs`` tells it from the prefill programs):
the leaf operations inside the decode program's runs whose named-scope
path holds ``kv_gather`` (every lane's rows gathered from the pool) or
``kv_write`` (the new token's rows scattered into it).

A device operation is named by its HLO instruction (``%fusion.153 =
...``). The program's ``step.scopes`` record maps the compiled decode
step's instruction names to their scope paths, from its op metadata;
the traced run records it when it attaches its span recorder. Where
less than half of the decode program's device time lies in operations
of any scope, the table does not describe what ran, and the reader
stays silent rather than guess."""

import bisect
import re

from bench.core import trace as tr
from bench.core.breakdown import traced_bounds
from bench.core.programs import split, step_programs

KV = {"kv_gather", "kv_write"}
INSTRUCTION = re.compile(r"^%?([\w.\-]+)(?: = |$)")
MIN_COVERED = 0.5


def scope_table(run):
    for s in run.spans_of("step.scopes"):
        if s.get("program") == "decode":
            return s.get("scopes")
    return None


def inside(ops, runs):
    """The operations that start within one of ``runs`` (sorted,
    disjoint (start, end) pairs), each clipped to its run's end."""
    starts = [s for s, _ in runs]
    for s, e, name in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < runs[i][1]:
            yield s, min(e, runs[i][1]), name


def read(run):
    if run.trace is None or run.traced_counts is None or not run.trace.host:
        return None
    table = scope_table(run)
    if not table or not run.trace.devices:
        return None
    dev = run.trace.devices[0]
    lo, hi = traced_bounds(run)
    groups = step_programs(run.trace.modules.get(dev, ()), lo, hi)
    dec, _ = split(groups, run.traced_delta("decode_steps"),
                   run.traced_delta("prefill_steps"))
    if dec is None:
        return None
    n_runs, ns = groups[dec]
    runs = sorted((s, e) for s, e, key in run.trace.modules[dev]
                  if key == dec and lo <= s <= hi)
    covered = kv = 0.0
    for s, e, name in inside(tr.leaves(run.trace.ops.get(dev, ())), runs):
        m = INSTRUCTION.match(name)
        path = table.get(m.group(1)) if m else None
        if path is None:
            continue
        covered += e - s
        if KV & set(path.split("/")):
            kv += e - s
    if covered < MIN_COVERED * ns:
        return None
    return kv / n_runs / 1e6
