"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error, never a default: a share
of a peak computed against the wrong chip is a wrong number.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float   # FLOP/s, dense bf16 matrix units
    hbm_bytes_s: float  # HBM bandwidth, bytes/s
    source: str


_V5E = Peak(bf16_flops=197e12, hbm_bytes_s=819e9,
            source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                   'bf16, 16 GB HBM at 819 GB/s per chip')

#: device_kind -> peaks; JAX names a v5e chip "TPU v5 lite"
PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
