"""Architecture configs + input-shape cells."""

from .archs import ARCHS, get_arch, smoke_config
from .base import ArchConfig, MLAConfig, MoEConfig, SSMConfig, ShapeCell

__all__ = [
    "ARCHS", "get_arch", "smoke_config", "ArchConfig", "MLAConfig",
    "MoEConfig", "SSMConfig", "ShapeCell",
]
