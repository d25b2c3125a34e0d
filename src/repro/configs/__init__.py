"""Architecture configs (assigned pool + demo) and shape sets."""

from repro.configs.base import (
    ARCH_MODULES,
    MeshConfig,
    MLAConfig,
    MoEConfig,
    ModelConfig,
    RWKVConfig,
    RunConfig,
    SHAPES,
    SMOKE_SHAPE,
    SSMConfig,
    ShapeConfig,
    TrainConfig,
    available_archs,
    get_config,
    reduce_for_smoke,
)

ASSIGNED_ARCHS = [
    "seamless-m4t-large-v2",
    "rwkv6-1.6b",
    "deepseek-v2-236b",
    "mixtral-8x7b",
    "nemotron-4-340b",
    "granite-3-8b",
    "yi-34b",
    "phi3-medium-14b",
    "qwen2-vl-7b",
    "zamba2-7b",
]

__all__ = [
    "ARCH_MODULES",
    "ASSIGNED_ARCHS",
    "MeshConfig",
    "MLAConfig",
    "MoEConfig",
    "ModelConfig",
    "RWKVConfig",
    "RunConfig",
    "SHAPES",
    "SMOKE_SHAPE",
    "SSMConfig",
    "ShapeConfig",
    "TrainConfig",
    "available_archs",
    "get_config",
    "reduce_for_smoke",
]
