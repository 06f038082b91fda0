"""Share of decode lanes that produced a token, over the window's decode
steps: tokens produced by decode steps (every token after a request's
first) divided by decode steps times lanes. Decode steps are the
program's ``SchedulerStats`` counter; tokens are stamped by the logits
hook."""


def read(run):
    steps = run.delta("decode_steps")
    if not steps:
        return None
    made = sum(1 for _, n, t in run.token_events() if n > 0 and run.in_window(t))
    return 100.0 * made / (steps * run.cfg.serving.lanes)
