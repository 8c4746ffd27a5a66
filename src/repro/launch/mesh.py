"""Production mesh construction.

Defined as FUNCTIONS so importing this module never touches jax device
state.  Single pod: 16x16 = 256 chips (v5e pod, 2D ICI torus).  Multi-pod:
2 pods x 256 chips with a leading "pod" axis over DCN.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "HardwareSpec", "V5E"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


class HardwareSpec:
    """Roofline constants for the target chip."""

    def __init__(self, name: str, peak_flops: float, hbm_bw: float,
                 ici_bw: float, hbm_bytes: float, dcn_bw: float = 25e9):
        self.name = name
        self.peak_flops = peak_flops      # bf16 FLOP/s per chip
        self.hbm_bw = hbm_bw              # bytes/s per chip
        self.ici_bw = ici_bw              # bytes/s per ICI link
        self.hbm_bytes = hbm_bytes        # HBM capacity per chip
        self.dcn_bw = dcn_bw              # bytes/s per chip across pods


V5E = HardwareSpec("tpu-v5e", peak_flops=197e12, hbm_bw=819e9,
                   ici_bw=50e9, hbm_bytes=16 * 2**30)
