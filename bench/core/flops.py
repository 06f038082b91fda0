"""Operations and bytes the algorithm needs, computed from shapes.

These are the numerators of every utilization the benchmark reports.
They count the work the model's equations require for the rows that are
live, never what a program happens to compute: no padded rows, no
gathered-but-masked cache rows, no recomputation.
"""

from __future__ import annotations

from bench.core.config import ModelSizes


def layer_matmul_flops(m: ModelSizes) -> int:
    """FLOPs of one token through one layer's projections and FFN."""
    q = 2 * m.hidden * m.heads * m.head_dim
    kv = 2 * 2 * m.hidden * m.kv_heads * m.head_dim
    o = 2 * m.heads * m.head_dim * m.hidden
    ffn = 3 * 2 * m.hidden * m.intermediate
    return q + kv + o + ffn


def attention_flops(m: ModelSizes, keys: int) -> int:
    """FLOPs of one query token attending over ``keys`` positions, all
    layers: scores (QK) and the weighted sum (PV), 2 FLOPs per MAC."""
    return m.layers * 4 * m.heads * m.head_dim * keys


def unembed_flops(m: ModelSizes) -> int:
    return 2 * m.hidden * m.vocab


def span_flops(m: ModelSizes, start: int, n: int, logits_rows: int) -> int:
    """FLOPs of ``n`` consecutive tokens at positions start..start+n-1,
    each attending causally over every position up to its own, with
    ``logits_rows`` of them unembedded."""
    if n <= 0:
        return 0
    # sum over p of (p + 1) keys for p in [start, start + n)
    keys = n * start + n * (n + 1) // 2
    return (
        n * m.layers * layer_matmul_flops(m)
        + attention_flops(m, keys)
        + logits_rows * unembed_flops(m)
    )


def decode_token_flops(m: ModelSizes, position: int) -> int:
    """One decoded token at ``position``: its row is unembedded."""
    return span_flops(m, position, 1, 1)


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
