"""Architecture registry: exact assigned configs + reduced smoke variants."""

from __future__ import annotations

import importlib

from repro.models.config import ModelConfig

_MODULES = {
    "gemma3-1b": "repro.configs.gemma3_1b",
    "granite-3-2b": "repro.configs.granite_3_2b",
    "chatglm3-6b": "repro.configs.chatglm3_6b",
    "granite-20b": "repro.configs.granite_20b",
    "mixtral-8x7b": "repro.configs.mixtral_8x7b",
    "granite-moe-1b-a400m": "repro.configs.granite_moe_1b_a400m",
    "jamba-1.5-large-398b": "repro.configs.jamba_1_5_large_398b",
    "falcon-mamba-7b": "repro.configs.falcon_mamba_7b",
    "llama-3.2-vision-11b": "repro.configs.llama_3_2_vision_11b",
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "granite-4.0-h-micro": "repro.configs.granite_4_0_h_micro",
}

ARCH_IDS = tuple(_MODULES)


def get_config(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise ValueError(f"unknown arch {name!r}; available: {ARCH_IDS}")
    return importlib.import_module(_MODULES[name]).CONFIG


def get_reduced(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise ValueError(f"unknown arch {name!r}; available: {ARCH_IDS}")
    return importlib.import_module(_MODULES[name]).reduced()
