"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` JAX reports. A kind that is not in the table is an error,
never a default: a share of a peak taken against the wrong chip's peak
is a wrong number that looks right.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_bf16: float       # FLOP/s, dense bf16 matmul
    hbm_bytes_s: float      # bytes/s, HBM bandwidth
    hbm_bytes: float        # bytes of HBM per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peak(flops_bf16=197e12, hbm_bytes_s=819e9,
                        hbm_bytes=16e9,
                        source="Google Cloud documentation, 'TPU v5e'"),
}


def peak_for(device_kind: str) -> Peak:
    """The peak of `device_kind`; raises KeyError for a kind the table
    does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
