from repro.configs.archs import reduced
from repro.configs.base import (EncoderConfig, LayerSpec, MLAConfig,
                                ModelConfig, MoEConfig, RGLRUConfig,
                                RopeScaling, SSMConfig, ShapeConfig,
                                StrategyConfig, strategy)
from repro.configs.registry import ARCHS, SKIPS, all_cells, get_arch, is_skipped
from repro.configs.shapes import SHAPES, get_shape

__all__ = [
    "ARCHS", "SHAPES", "SKIPS", "EncoderConfig", "LayerSpec", "MLAConfig",
    "ModelConfig", "MoEConfig", "RGLRUConfig", "RopeScaling", "SSMConfig",
    "ShapeConfig", "StrategyConfig",
    "all_cells", "get_arch", "get_shape", "is_skipped", "reduced", "strategy",
]
