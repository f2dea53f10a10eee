"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``. A device that is not listed is an error, never
a default: a CPU or an unlisted chip has no peak to divide by.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s, dense bf16 matmul
    int8_ops: float          # OP/s, int8
    hbm_bytes: float         # HBM capacity
    hbm_bytes_per_s: float   # HBM bandwidth
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9,
        hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e" (system architecture)'),
}


def peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
