"""Median host turnaround between two decode steps of one round: from
the end of one step's ``logits_fetch`` (the host holds its logits, so
the device has finished it) to the return of the next step's
``decode_dispatch`` (the next step is queued on the device), over the
steps whose dispatch returned in the window. It is the host's sampling
and dispatch work between two steps, read from the program's round-phase
records (``round.<phase>``, each with the round it belongs to)."""

from bench.core.stats import percentile

FETCH = "round.logits_fetch"
DISPATCH = "round.decode_dispatch"


def read(run):
    if run.spans is None:
        return None
    steps = sorted(
        (s for s in run.spans if s.get("phase") in (FETCH, DISPATCH)),
        key=lambda s: s["t0"],
    )
    turns = []
    fetched = None  # the latest fetch not yet followed by a dispatch
    for s in steps:
        if s["phase"] == FETCH:
            fetched = s
            continue
        if (fetched is not None and fetched.get("round") == s.get("round")
                and run.in_window(s["t1"])):
            turns.append((s["t1"] - fetched["t1"]) * 1e3)
        fetched = None
    if not turns:
        return None
    return percentile(turns, 50)
