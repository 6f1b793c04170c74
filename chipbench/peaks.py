"""Published peaks per chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
A kind missing from the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    int8_ops: float          # operations per second
    bf16_flops: float
    hbm_bytes_per_s: float
    hbm_bytes: float


V5E = Peaks(int8_ops=393e12, bf16_flops=197e12, hbm_bytes_per_s=819e9,
            hbm_bytes=16e9)

TABLE = {"TPU v5 lite": V5E}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return TABLE[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(TABLE)}") from None
