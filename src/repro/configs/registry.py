"""Architecture registry: --arch <id> resolution for every launcher."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import ModelConfig

_MODULES: Dict[str, str] = {
    "zamba2-7b": "repro.configs.zamba2_7b",
    "rwkv6-1.6b": "repro.configs.rwkv6_1_6b",
    "starcoder2-3b": "repro.configs.starcoder2_3b",
    "qwen3-1.7b": "repro.configs.qwen3_1_7b",
    "qwen1.5-4b": "repro.configs.qwen1_5_4b",
    "deepseek-7b": "repro.configs.deepseek_7b",
    "deepseek-v3-671b": "repro.configs.deepseek_v3_671b",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "moonlight-16b-a3b": "repro.configs.moonlight_16b_a3b",
    "pixtral-12b": "repro.configs.pixtral_12b",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
}


def list_archs() -> List[str]:
    return list(_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list_archs()}")
    return importlib.import_module(_MODULES[arch]).CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {list_archs()}")
    return importlib.import_module(_MODULES[arch]).smoke_config()
