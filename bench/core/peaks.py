"""Published peaks of each chip the benchmark may run on, keyed by the
``device_kind`` JAX reports. A chip not in the table is an error: a share
of a peak computed against a guessed peak means nothing."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s, dense bf16 matmul
    hbm_bytes: float  # bytes/s, HBM bandwidth
    hbm_capacity: int  # bytes of HBM
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        hbm_bytes=819e9,
        hbm_capacity=16 * 10**9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "819 GB/s HBM, 16 GB HBM per chip",
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}"
        ) from None
