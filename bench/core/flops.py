"""Operations and bytes of single calls, computed from shapes, and the
roofline they set; whatever the family.

These are numerators of the utilizations the benchmark reports. A
family's whole-model FLOP count lives in its family module
(``bench/reference/<reference>.py``); like these, it counts the work the
equations require for the rows that are live, never what a program
happens to compute: no padded rows, no gathered-but-masked cache rows,
no recomputation.
"""

from __future__ import annotations


def packed_matmul_cost(
    rows: int, k: int, n: int, bits: int, act_bytes: int = 2
) -> tuple[int, int]:
    """(FLOPs, bytes) of out[rows, n] = x[rows, k] @ decode(w[k, n]) *
    scale[n] with ``w`` a packed carrier of ``bits`` per weight.

    Bytes: the carrier (k * n * bits / 8), the f32 scales, the
    activations read in the model dtype and the f32 output written.
    """
    flops = 2 * rows * k * n
    carrier = k * n * bits // 8
    moved = carrier + 4 * n + rows * k * act_bytes + rows * n * 4
    return flops, moved


def roofline_seconds(
    flops: float, moved: float, peak_flops: float, peak_bytes: float
) -> tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_compute = flops / peak_flops
    t_memory = moved / peak_bytes
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
