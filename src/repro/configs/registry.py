"""Architecture registry: ``--arch <id>`` resolution.

Each assigned architecture lives in its own module (src/repro/configs/<id>.py)
with the exact public-literature geometry; this registry maps ids to configs.
"""
from __future__ import annotations

from .base import ModelConfig


def _load() -> dict[str, ModelConfig]:
    from . import (llama3_2_3b, moonshot_v1_16b_a3b, pixtral_12b, qwen2_5_3b,
                   qwen3_8b, qwen3_moe_30b_a3b, rwkv6_3b, stablelm_1_6b,
                   whisper_base, zamba2_2_7b)
    mods = [rwkv6_3b, stablelm_1_6b, qwen2_5_3b, qwen3_8b, llama3_2_3b,
            zamba2_2_7b, moonshot_v1_16b_a3b, qwen3_moe_30b_a3b, pixtral_12b,
            whisper_base]
    return {m.CONFIG.name: m.CONFIG for m in mods}


_REGISTRY: dict[str, ModelConfig] | None = None


def get_config(name: str) -> ModelConfig:
    global _REGISTRY
    if _REGISTRY is None:
        _REGISTRY = _load()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_archs() -> list[str]:
    global _REGISTRY
    if _REGISTRY is None:
        _REGISTRY = _load()
    return sorted(_REGISTRY)
