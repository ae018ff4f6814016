"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

TPU v5e: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
interconnect.  A copy of the program's ``repro.roofline.analysis.PEAKS``
kept with the benchmark, so that no change to the program moves the
yardstick.  A kind that is not here is an error, never a default.
"""
from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_bf16: float  # FLOP/s
    hbm_bytes_per_s: float  # B/s
    hbm_bytes: float  # capacity, B


PEAKS = {
    "TPU v5 lite": Peaks(flops_bf16=197e12, hbm_bytes_per_s=819e9,
                         hbm_bytes=16e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks recorded for device kind {device_kind!r} "
            f"(known: {', '.join(sorted(PEAKS))})"
        ) from None
