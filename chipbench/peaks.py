"""Published per-chip peaks, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bfloat16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s.  A kind that is not in the
table is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float  # FLOP/s: the bfloat16 rate, the chip's fastest float
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9),
}


def peaks(kind: str) -> Peaks:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
