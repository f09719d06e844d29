"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A device that is not in the table is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s
inter-chip interconnect per chip.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float      # FLOP/s
    hbm_bytes_s: float     # bytes/s
    hbm_bytes: float       # bytes
    ici_bytes_s: float     # bytes/s per chip
    source: str


TPU_V5E = Peaks(bf16_flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9,
                ici_bytes_s=1600e9 / 8,
                source='Google Cloud documentation, "TPU v5e"')

PEAKS = {"TPU v5 lite": TPU_V5E}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to PEAKS with its "
                       f"source") from None
