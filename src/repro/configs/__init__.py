"""Config registry: ``get_config("<arch-id>")`` / ``--arch <id>``."""
from __future__ import annotations

from repro.configs.base import (
    FLConfig,
    INPUT_SHAPES,
    InputShape,
    LoRAConfig,
    MeshConfig,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    RWKVConfig,
    SSMConfig,
    TrainConfig,
    YarnConfig,
)

from repro.configs.h2o_danube_1p8b import CONFIG as _h2o
from repro.configs.zamba2_1p2b import CONFIG as _zamba2
from repro.configs.phi3_vision_4p2b import CONFIG as _phi3v
from repro.configs.deepseek_v2_236b import CONFIG as _dsv2
from repro.configs.nemotron_4_340b import CONFIG as _nemotron
from repro.configs.qwen2_7b import CONFIG as _qwen2
from repro.configs.whisper_large_v3 import CONFIG as _whisper
from repro.configs.rwkv6_7b import CONFIG as _rwkv6
from repro.configs.mixtral_8x7b import CONFIG as _mixtral
from repro.configs.llama3_405b import CONFIG as _llama3
from repro.configs.flude_paper import CONFIG as _flude_paper

_REGISTRY = {
    c.name: c
    for c in [
        _h2o, _zamba2, _phi3v, _dsv2, _nemotron,
        _qwen2, _whisper, _rwkv6, _mixtral, _llama3, _flude_paper,
    ]
}

ASSIGNED_ARCHS = [
    "h2o-danube-1.8b", "zamba2-1.2b", "phi-3-vision-4.2b", "deepseek-v2-236b",
    "nemotron-4-340b", "qwen2-7b", "whisper-large-v3", "rwkv6-7b",
    "mixtral-8x7b", "llama3-405b",
]


def get_config(name: str) -> ModelConfig:
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_configs():
    return sorted(_REGISTRY)


__all__ = [
    "ASSIGNED_ARCHS", "FLConfig", "INPUT_SHAPES", "InputShape", "MeshConfig",
    "LoRAConfig", "MLAConfig", "ModelConfig", "MoEConfig", "RWKVConfig", "SSMConfig",
    "TrainConfig", "YarnConfig", "get_config", "list_configs",
]
