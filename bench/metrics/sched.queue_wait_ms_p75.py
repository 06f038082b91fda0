"""75th percentile of queue wait, from a request's due time to its
admission, read from the program's own queue spans (``SpanRecorder``;
the benchmark opens each at the due time through ``submit``). A request
due in the window and never admitted counts as missing."""

from bench.core.stats import percentile


def read(run):
    if run.spans is None:
        return None
    wait = {s["rid"]: s["t1"] - s["t0"] for s in run.spans_of("queue")}
    return percentile(
        [wait[r] * 1e3 if r in wait else None for r in run.window.attempted],
        75,
    )
