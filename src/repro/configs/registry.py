"""Architecture registry: --arch <id> -> (full config, smoke config)."""
from __future__ import annotations

import importlib
from typing import Dict

from repro.configs.base import ModelConfig

_ARCH_MODULES = {
    "granite-moe-3b-a800m": "repro.configs.granite_moe_3b_a800m",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "qwen3-8b": "repro.configs.qwen3_8b",
    "qwen3-14b": "repro.configs.qwen3_14b",
    "deepseek-7b": "repro.configs.deepseek_7b",
    "qwen2.5-32b": "repro.configs.qwen2_5_32b",
    "xlstm-125m": "repro.configs.xlstm_125m",
    "seamless-m4t-large-v2": "repro.configs.seamless_m4t_large_v2",
    "pixtral-12b": "repro.configs.pixtral_12b",
    "zamba2-7b": "repro.configs.zamba2_7b",
    "moonlight-16b-a3b": "repro.configs.moonlight_16b_a3b",
    "m6-base": "repro.configs.m6",
    "m6-10b": "repro.configs.m6",
    "m6-100b": "repro.configs.m6",
    "m6-1t": "repro.configs.m6",
}

_M6_ATTR = {"m6-base": "M6_BASE", "m6-10b": "M6_10B",
            "m6-100b": "M6_100B", "m6-1t": "M6_1T"}

# Served only through the paged engine: latent attention has no dense
# (B, max_len) decode cache, which the smoke tests and the dry-run drive.
_PAGED_ONLY = ("moonlight-16b-a3b",)

ARCH_IDS = [a for a in _ARCH_MODULES
            if not a.startswith("m6") and a not in _PAGED_ONLY]
ALL_IDS = list(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(_ARCH_MODULES[arch])
    if arch in _M6_ATTR:
        return getattr(mod, _M6_ATTR[arch])
    return mod.CONFIG


def get_smoke_config(arch: str) -> ModelConfig:
    mod = importlib.import_module(_ARCH_MODULES[arch])
    return mod.smoke()


def get_module(arch: str):
    return importlib.import_module(_ARCH_MODULES[arch])
