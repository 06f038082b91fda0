"""Output tokens whose logits reached the host inside the window, divided
by the window's length."""

from bench.core.stats import rate


def read(run):
    n = sum(1 for _, _, t in run.token_events() if run.in_window(t))
    return rate(n, run.window.seconds)
