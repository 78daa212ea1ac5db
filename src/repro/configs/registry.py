"""Architecture registry: ``--arch <id>`` resolution.

Arch ids keep the assignment spelling (dashes/dots); module names use
underscores.
"""
from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig

_ARCH_MODULES: dict[str, str] = {
    "seamless-m4t-medium": "repro.configs.seamless_m4t_medium",
    "mixtral-8x7b": "repro.configs.mixtral_8x7b",
    "moonshot-v1-16b-a3b": "repro.configs.moonshot_v1_16b_a3b",
    "moonshot-v1-16b-a3b-ep8": "repro.configs.moonshot_v1_16b_a3b_ep8",
    "qwen2.5-32b": "repro.configs.qwen2_5_32b",
    "deepseek-7b": "repro.configs.deepseek_7b",
    "internlm2-20b": "repro.configs.internlm2_20b",
    "qwen3-1.7b": "repro.configs.qwen3_1_7b",
    "zamba2-1.2b": "repro.configs.zamba2_1_2b",
    "internvl2-26b": "repro.configs.internvl2_26b",
    "rwkv6-1.6b": "repro.configs.rwkv6_1_6b",
}

ARCH_IDS: tuple[str, ...] = tuple(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(
            f"unknown arch {arch!r}; available: {', '.join(ARCH_IDS)}"
        )
    mod = importlib.import_module(_ARCH_MODULES[arch])
    return mod.CONFIG


def all_configs() -> dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
