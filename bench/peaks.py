"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e ("TPU v5 lite" as JAX reports it): Google Cloud documentation,
"TPU v5e" system architecture table: 197 TFLOP/s bf16 and 819 GB/s of HBM
bandwidth per chip.  A device that is not in the table is an error, not a
default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float      # FLOP/s
    hbm_bw: float          # bytes/s


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9),
}


def peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
