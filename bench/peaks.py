"""Published peak rates per chip, keyed by the ``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s per chip).  A device kind missing here is an
error, never a default: a roofline against a guessed peak means nothing.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float        # FLOP/s per chip
    hbm_bytes_per_s: float   # HBM bandwidth per chip
    hbm_bytes: float         # HBM capacity per chip


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None
