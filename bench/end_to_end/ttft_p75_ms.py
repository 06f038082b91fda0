"""75th percentile, over every request due in the window, of the time
from its due time to its first token reaching the host. A request that
never produced one counts as missing (+inf). The 75th is the highest
percentile the open-loop cells support with ten requests beyond it: the
doc cell sends 41 requests in a 51-s window at 0.8 req/s."""

from bench.core.stats import percentile


def read(run):
    w = run.window
    ttft = []
    for rid in w.attempted:
        times = w.tokens.get(rid)
        ttft.append((times[0] - w.due[rid]) * 1e3 if times else None)
    return percentile(ttft, 75)
