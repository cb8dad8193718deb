"""Architecture configs: one module per assigned arch + registry."""
from .base import (SHAPES, ArchConfig, MLACfg, MoECfg, SSMCfg, ShapeSpec,
                   YarnCfg, supports)
from .registry import ARCHS, get_config

__all__ = ["SHAPES", "ArchConfig", "MLACfg", "MoECfg", "SSMCfg", "ShapeSpec",
           "YarnCfg",
           "supports", "ARCHS", "get_config"]
