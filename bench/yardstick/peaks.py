"""Published per-chip peaks, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in
bfloat16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s.

The benchmark keeps its own table so that the yardstick does not move when
the program's code does. A device kind missing from it is an error, not a
default: a share computed against another chip's peaks is a wrong number.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None
