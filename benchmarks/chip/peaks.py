"""Published peaks of each chip, keyed by the ``device_kind`` JAX reports.

An unknown kind is an error, never a default: a share of a peak that was
not published for the chip it ran on means nothing.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float       # dense bf16 FLOP/s of one chip
    hbm_bytes_per_s: float  # HBM bandwidth of one chip
    hbm_bytes: float        # HBM capacity of one chip
    source: str


_V5E = Peak(
    bf16_flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
    source="Google Cloud TPU documentation, 'TPU v5e': 197 TFLOP/s bf16, "
           "16 GB HBM at 819 GB/s per chip")

PEAKS: dict[str, Peak] = {
    "TPU v5 lite": _V5E,  # what jax reports as device_kind on a v5e
    "TPU v5e": _V5E,
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
