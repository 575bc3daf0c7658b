"""Architecture registry: ``--arch <id>`` resolution for every launcher."""
from __future__ import annotations

import importlib

from repro.models.lm import LMConfig

_MODULES = {
    "whisper-tiny": "repro.configs.whisper_tiny",
    "zamba2-1.2b": "repro.configs.zamba2_1p2b",
    "qwen1.5-0.5b": "repro.configs.qwen1p5_0p5b",
    "minicpm3-4b": "repro.configs.minicpm3_4b",
    "starcoder2-3b": "repro.configs.starcoder2_3b",
    "granite-8b": "repro.configs.granite_8b",
    "deepseek-moe-16b": "repro.configs.deepseek_moe_16b",
    "deepseek-v2-lite": "repro.configs.deepseek_v2_lite",
    "granite-moe-3b-a800m": "repro.configs.granite_moe_3b",
    "rwkv6-7b": "repro.configs.rwkv6_7b",
    "llava-next-mistral-7b": "repro.configs.llava_next_mistral_7b",
}

ARCH_IDS = tuple(_MODULES)


def _mod(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {', '.join(ARCH_IDS)}")
    return importlib.import_module(_MODULES[arch])


def get_config(arch: str) -> LMConfig:
    return _mod(arch).config()


def get_smoke_config(arch: str) -> LMConfig:
    return _mod(arch).smoke_config()
