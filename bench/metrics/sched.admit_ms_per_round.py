"""Host time of the scheduler's admission loop per round: the program's
``round.admit`` records (prefix lookup, adoption and copy-on-write,
single-step prefills with their logits fetch and first sample) of the
rounds that started in the window, summed, over those rounds."""


def read(run):
    if run.spans is None:
        return None
    admits = [s for s in run.spans_of("round.admit") if run.in_window(s["t0"])]
    if not admits:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in admits) / len(admits)
