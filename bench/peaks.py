"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  A kind that is not here is an error, never a
default.  (Copied from the program's ``roofline/analysis.py`` so that a
change there cannot move the benchmark's yardstick.)"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    flops: float            # FLOP/s, bf16
    hbm_bw: float           # HBM bytes/s
    link_bw: float          # bytes/s of one ICI link
    source: str


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s; ICI 1,600 Gbit/s per chip, taken "
               "as one 50 GB/s link"),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known kinds: {sorted(PEAKS)}") from None
