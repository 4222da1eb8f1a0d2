"""Published per-chip peaks, keyed by the `device_kind` JAX reports.

A device kind that is not in the table is an error, never a default: a
share of a peak is only as good as the peak it divides by.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops: float   # dense bf16 FLOP/s per chip
    hbm_bw: float  # HBM bytes/s per chip
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
