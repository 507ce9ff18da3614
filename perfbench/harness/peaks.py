"""Peak numbers of one chip, keyed by ``jax.Device.device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture): 197
TFLOP/s bf16, 16 GB of HBM at 819 GB/s. A device kind that is not here is
an error: no share is ever taken of another chip's peaks.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float      # FLOP/s
    hbm_bandwidth: float   # B/s
    hbm_bytes: int
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, 16 * 10**9,
                         "Google Cloud documentation, 'TPU v5e'"),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak numbers for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
