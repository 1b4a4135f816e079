"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s (bf16),
819 GB/s of HBM bandwidth, 16 GB of HBM per chip. JAX reports a v5e as
"TPU v5 lite". A kind that is not in the table is an error, never a
default: a share of a peak is only meaningful against the right chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


_V5E = Peak(flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
            source='Google Cloud documentation, "TPU v5e"')

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
