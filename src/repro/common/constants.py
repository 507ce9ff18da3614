"""Per-chip peak numbers, keyed by ``jax.devices()[0].device_kind``, and
the production mesh layout.

Source of every peak: Google Cloud documentation, "TPU v5e" (system
architecture page): 197 TFLOP/s bf16, 16 GB of HBM2 at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect per chip over 4 ICI links (2-D
torus). A device kind that is not in the table raises: the
roofline never divides by another chip's peaks.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float        # FLOP/s, dense bf16 matmul
    hbm_bandwidth: float     # B/s
    hbm_bytes: int           # B of HBM per chip
    ici_bandwidth: float     # B/s per chip, all links, one direction
    ici_links: int
    source: str

    @property
    def ici_bandwidth_per_link(self) -> float:
        return self.ici_bandwidth / self.ici_links


PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bandwidth=819e9,
        hbm_bytes=16 * 10**9,
        ici_bandwidth=1600e9 / 8,
        ici_links=4,
        source="Google Cloud documentation, 'TPU v5e'",
    ),
}

# The chip this repository targets (what `device_kind` reads on a v5e).
TARGET_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """The peak table row for ``device_kind``; an unknown kind raises."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak numbers for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}"
        ) from None


# Production mesh shape (per pod).
POD_MESH_SHAPE = (16, 16)
POD_MESH_AXES = ("data", "model")
MULTIPOD_MESH_SHAPE = (2, 16, 16)
MULTIPOD_MESH_AXES = ("pod", "data", "model")

# Mesh axis names used throughout the framework.
AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_MODEL = "model"
