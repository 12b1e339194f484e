from .base import (SHAPES, ArchEntry, InputShape, MLAConfig, ModelConfig,
                   MoEConfig, RecurrentConfig, YarnConfig, get_arch,
                   list_archs, register, shapes_for)

__all__ = ["SHAPES", "ArchEntry", "InputShape", "MLAConfig", "ModelConfig",
           "MoEConfig", "RecurrentConfig", "YarnConfig", "get_arch",
           "list_archs", "register", "shapes_for"]
