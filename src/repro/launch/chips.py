"""Published peaks of the accelerators this repo targets, keyed by
``jax.Device.device_kind``.

The one table every roofline, cost seed and utilization figure reads. A
device that is not listed is an error, never a default: a CPU or an
unlisted chip has no peak this repo can vouch for.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s, dense bf16 matmul
    int8_ops: float          # OP/s, int8
    hbm_bytes: float         # HBM capacity
    hbm_bytes_per_s: float   # HBM bandwidth
    ici_bytes_per_s: float   # chip-to-chip interconnect, all links of a chip
    source: str


CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9,
        hbm_bytes_per_s=819e9, ici_bytes_per_s=1600e9 / 8,
        source='Google Cloud documentation, "TPU v5e" (system architecture)'),
}


def chip_peaks(device_kind: str | None = None) -> ChipPeaks:
    """Peaks of ``device_kind`` (default: the first visible JAX device)."""
    kind = device_kind if device_kind is not None else \
        jax.devices()[0].device_kind
    try:
        return CHIP_PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(CHIP_PEAKS)}") from None
