"""Architecture registry: the assigned architectures, and chip shares of them.

Each module exposes ``config()`` (the exact published configuration) and
``smoke_config()`` (a reduced same-family configuration for CPU smoke tests).
"""
from __future__ import annotations

from typing import Callable, Dict

from repro.models.config import ModelConfig

from . import (dbrx_132b, deepseek_moe_16b, gemma3_4b, gemma3_27b,
               mamba2_2p7b, moonlight_16b_a3b, musicgen_large, paligemma_3b,
               qwen1p5_4b, yi_9b, zamba2_1p2b)

_MODULES = {
    "zamba2-1.2b": zamba2_1p2b,
    "mamba2-2.7b": mamba2_2p7b,
    "gemma3-27b": gemma3_27b,
    "qwen1.5-4b": qwen1p5_4b,
    "gemma3-4b": gemma3_4b,
    "yi-9b": yi_9b,
    "dbrx-132b": dbrx_132b,
    "deepseek-moe-16b": deepseek_moe_16b,
    "musicgen-large": musicgen_large,
    "paligemma-3b": paligemma_3b,
    "moonlight-16b-a3b": moonlight_16b_a3b,
}

REGISTRY: Dict[str, Callable[[], ModelConfig]] = {
    name: mod.config for name, mod in _MODULES.items()}
SMOKE_REGISTRY: Dict[str, Callable[[], ModelConfig]] = {
    name: mod.smoke_config for name, mod in _MODULES.items()}
# one chip's share of an expert-parallel deployment (the benchmark's
# ``program_preset``)
REGISTRY["moonlight-16b-a3b-ep8"] = moonlight_16b_a3b.ep8_config
SMOKE_REGISTRY["moonlight-16b-a3b-ep8"] = moonlight_16b_a3b.ep8_smoke_config

ARCH_NAMES = list(REGISTRY)


def get_config(name: str) -> ModelConfig:
    return REGISTRY[name]()


def get_smoke_config(name: str) -> ModelConfig:
    return SMOKE_REGISTRY[name]()
