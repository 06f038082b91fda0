"""Share of the window's prompt tokens served from the prefix cache:
``prefix_hit_tokens / (prefix_hit_tokens + prefill_tokens)`` of the
program's ``SchedulerStats`` between the window's open and close."""


def read(run):
    hit = run.delta("prefix_hit_tokens")
    total = hit + run.delta("prefill_tokens")
    if not total:
        return None
    return 100.0 * hit / total
