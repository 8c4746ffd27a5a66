"""The paper's experimental configuration (``cam_paper``), as data."""
from repro.configs import cam_paper

__all__ = ["cam_paper"]
