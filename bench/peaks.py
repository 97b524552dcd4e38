"""Published peaks of one accelerator chip, keyed by JAX's ``device_kind``.

Source: Google Cloud TPU documentation, "TPU v5e" (per-chip peak compute
and HBM bandwidth). A kind that is not in the table is an error: a roofline
share against a guessed peak is no measurement.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    bf16_flops: float        # FLOP/s, dense bf16 matrix multiplication
    hbm_bytes: float         # bytes/s, HBM bandwidth
    hbm_capacity: float      # bytes
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16e9,
                         'Google Cloud TPU docs, "TPU v5e": 197 TFLOP/s bf16, '
                         "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
