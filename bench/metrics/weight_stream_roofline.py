"""Share of its roofline that the Pallas ``weight_stream`` kernel reaches
on the decode step's streamed FFN layers: the least time the chip could
take for the calls the profiler saw, over their device time.

Each decode step runs the kernel once per packed matrix of a streamed
layer, on the step's lanes; the configuration's family module lists
those matrices (``streamed_matmuls``: in the llama family w1 and w3,
hidden -> intermediate, and w2, intermediate -> hidden). The least time
of a call is the larger of its FLOPs over the bf16 peak and its bytes
(packed carrier, scales, activations in, f32 out) over HBM bandwidth
(``bench.core.flops``); at these shapes the bytes bound it."""

from bench.core import trace as tr
from bench.core.breakdown import traced_bounds
from bench.core.flops import packed_matmul_cost, roofline_seconds

# the kernel's own calls: HLO instructions named after ops.stream_matmul
KERNEL = r"^%stream_matmul(\.\d+)? = "


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    streamed = getattr(run.cfg.family, "streamed_matmuls", None)
    shapes = streamed(run.cfg.sizes) if streamed else ()
    if not shapes:
        return None
    if not run.trace.host or not run.trace.devices:
        return None
    lo, hi = traced_bounds(run)
    calls = tr.matching(run.trace.ops.get(run.trace.devices[0], ()), KERNEL,
                        lo, hi)
    if not calls:
        return None
    lanes = run.cfg.serving.lanes
    least = 0.0
    for k, n, bits in shapes:
        flops, moved = packed_matmul_cost(lanes, k, n, bits)
        least += roofline_seconds(flops, moved, run.peaks.bf16_flops,
                                  run.peaks.hbm_bytes)[0]
    spent = sum(e - s for s, e, _ in calls) / 1e9
    return 100.0 * (least / len(shapes)) * len(calls) / spent
