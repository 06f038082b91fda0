"""The whole model step's share of the chip's bf16 peak: the FLOPs the
equations need for every prompt token prefilled and every token decoded
in the window (live rows only: causal attention over the positions up
to each token, no padding, no gathered cache rows past the sequence),
over the host time of the window's rounds times the peak.

Prefilled tokens come from the program's prefill spans (start and
length of each chunk); decoded tokens from the logits hook, each at its
own position. Only a prompt's last chunk needs its logits row. The FLOP
count is the configuration's family module's."""


def read(run):
    if run.peaks is None or run.spans is None:
        return None
    m = run.cfg.sizes
    span_flops = run.cfg.family.span_flops
    decode_token_flops = run.cfg.family.decode_token_flops
    flops = 0
    for s in run.spans_of("prefill"):
        if run.in_window(s["t1"]):
            start = s.get("chunk_start", 0)
            last = start + s["tokens"] == len(run.window.specs[s["rid"]].prompt)
            flops += span_flops(m, start, s["tokens"], int(last))
    for rid, n, t in run.token_events():
        if n > 0 and run.in_window(t):
            pos = len(run.window.specs[rid].prompt) + n - 1
            flops += decode_token_flops(m, pos)
    busy = sum(
        min(e, run.window.t_close) - s
        for s, e in run.window.rounds
        if s < run.window.t_close
    )
    if busy <= 0:
        return None
    return 100.0 * flops / (busy * run.peaks.bf16_flops)
