from .base import (
    EncDecConfig,
    ModelConfig,
    MoEConfig,
    SHAPES,
    SMOKE_SHAPE,
    ShapeConfig,
    SharedBlockConfig,
    SSMConfig,
    VLMConfig,
    reduced,
)
from .registry import ARCHS, cell_is_applicable, get_config, get_shape, list_archs

__all__ = [
    "EncDecConfig", "ModelConfig", "MoEConfig", "SHAPES", "SMOKE_SHAPE",
    "ShapeConfig", "SharedBlockConfig", "SSMConfig", "VLMConfig", "reduced",
    "ARCHS", "cell_is_applicable", "get_config", "get_shape", "list_archs",
]
