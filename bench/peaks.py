"""Peak rates of one chip, keyed by ``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and
16 GB of HBM at 819 GB/s per chip. A kind that is not in the table is an
error, never a default.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float       # bf16 FLOP/s per chip
    hbm_bytes_s: float  # HBM bytes/s per chip
    hbm_bytes: float   # HBM capacity per chip


V5E = Peaks(flops=197e12, hbm_bytes_s=819e9, hbm_bytes=16e9)

BY_KIND = {"TPU v5 lite": V5E, "TPU v5e": V5E}


def for_kind(kind: str) -> Peaks:
    try:
        return BY_KIND[kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {kind!r}; known: {sorted(BY_KIND)}"
        ) from None


def roofline_s(flops: float, nbytes: float, peaks: Peaks) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks.flops, nbytes / peaks.hbm_bytes_s)
