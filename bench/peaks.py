"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` that JAX reports.

A device that is not in the table is an error, not a default: a roofline
share against the wrong chip's peaks would be a wrong number.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float      # dense bf16 matrix-unit peak
    hbm_bytes_per_s: float  # HBM bandwidth
    hbm_bytes: float        # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM2 at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for a chip the
    table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add it to bench/peaks.py with "
                       f"its source") from None
