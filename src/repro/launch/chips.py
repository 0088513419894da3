"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in
bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, and 1,600 Gbit/s of
chip-to-chip interconnect.

A device kind missing from the table is an error, not a default: a
roofline share computed against another chip's peaks is a wrong number.
The dry-run compiles on host devices for a v5e target, so it names its
target kind explicitly (:data:`DRYRUN_TARGET`).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float        # FLOP/s
    hbm_bw: float            # bytes/s
    ici_bw: float            # bytes/s, all chip-to-chip links together


PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(bf16_flops=197e12, hbm_bw=819e9,
                             ici_bw=1600e9 / 8),
}

DRYRUN_TARGET = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip JAX reports as ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
