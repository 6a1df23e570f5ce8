from .base import (
    EncDecConfig,
    ModelConfig,
    MoEConfig,
    SMOKE_SHAPE,
    ShapeConfig,
    SharedBlockConfig,
    SSMConfig,
    VLMConfig,
    reduced,
)
from .registry import ARCHS, get_config, list_archs

__all__ = [
    "EncDecConfig", "ModelConfig", "MoEConfig", "SMOKE_SHAPE",
    "ShapeConfig", "SharedBlockConfig", "SSMConfig", "VLMConfig", "reduced",
    "ARCHS", "get_config", "list_archs",
]
