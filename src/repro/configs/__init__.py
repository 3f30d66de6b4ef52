from .base import DECODE_32K, TRAIN_4K, ModelConfig, ShapeConfig
from .registry import get_config, list_archs
