"""Inter-token gaps as the window sees them."""


def window_gaps_ms(run) -> list[float]:
    """Gaps between consecutive tokens of one request whose later token
    reached the host inside the window, in ms."""
    out = []
    for times in run.window.tokens.values():
        for a, b in zip(times, times[1:]):
            if run.in_window(b):
                out.append((b - a) * 1e3)
    return out
