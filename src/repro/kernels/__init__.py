"""Pallas TPU kernels for the two device steps of profile -> price.

* profile_grid — mixed-eps occupancy histograms (banded one-hot matmul).
* price_grid   — the fused policy fixed point, composition and argmin.

Off a TPU both run in interpret mode (``repro.backend.default_interpret``).
"""
from repro.kernels import price_grid, profile_grid

__all__ = ["profile_grid", "price_grid"]
