"""Architecture registry: ``get_config(arch_id)`` / ``--arch <id>``."""
from __future__ import annotations

import importlib

from repro.configs.base import (
    SHAPES,
    LayerSpec,
    MambaConfig,
    ModelConfig,
    MoEConfig,
    RWKVConfig,
    ShapeConfig,
    reduced,
    share,
    supports_shape,
)

# name -> (module, attribute): an architecture as published is its module's
# ``CONFIG``; a share (``base.share``, one chip's part of a stated deployment)
# is another attribute of the architecture's module
_CONFIGS = {
    "gemma3-4b": ("repro.configs.gemma3_4b", "CONFIG"),
    "smollm-360m": ("repro.configs.smollm_360m", "CONFIG"),
    "gemma2-27b": ("repro.configs.gemma2_27b", "CONFIG"),
    "qwen2-0.5b": ("repro.configs.qwen2_0_5b", "CONFIG"),
    "chameleon-34b": ("repro.configs.chameleon_34b", "CONFIG"),
    "deepseek-moe-16b": ("repro.configs.deepseek_moe_16b", "CONFIG"),
    "dbrx-132b": ("repro.configs.dbrx_132b", "CONFIG"),
    "jamba-1.5-large": ("repro.configs.jamba_1_5_large", "CONFIG"),
    "musicgen-large": ("repro.configs.musicgen_large", "CONFIG"),
    "rwkv6-7b": ("repro.configs.rwkv6_7b", "CONFIG"),
    "deepseek-moe-16b-ep4": ("repro.configs.deepseek_moe_16b", "EP4"),
}


def list_archs() -> list[str]:
    """The architectures as published (shares left out)."""
    return [arch for arch, (_, attr) in _CONFIGS.items() if attr == "CONFIG"]


def get_config(arch: str) -> ModelConfig:
    """An architecture as published, or one chip's share of one, by name."""
    if arch not in _CONFIGS:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_CONFIGS)}")
    module, attr = _CONFIGS[arch]
    return getattr(importlib.import_module(module), attr)


def get_shape(name: str) -> ShapeConfig:
    return SHAPES[name]


__all__ = [
    "SHAPES",
    "LayerSpec",
    "MambaConfig",
    "ModelConfig",
    "MoEConfig",
    "RWKVConfig",
    "ShapeConfig",
    "get_config",
    "get_shape",
    "list_archs",
    "reduced",
    "share",
    "supports_shape",
]
