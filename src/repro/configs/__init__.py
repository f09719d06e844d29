"""Architecture registry: ``get_config(arch_id)`` / ``list_archs()``.

The 10 assigned architectures plus the paper's own eval model (llama2-70b).
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401  (re-exported)
    INPUT_SHAPES,
    EncoderConfig,
    FrontendStub,
    InputShape,
    MambaConfig,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    XLSTMConfig,
    YarnConfig,
)

_ARCH_MODULES = {
    "phi3-medium-14b": "phi3_medium_14b",
    "internvl2-1b": "internvl2_1b",
    "minicpm-2b": "minicpm_2b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "starcoder2-3b": "starcoder2_3b",
    "arctic-480b": "arctic_480b",
    "xlstm-1.3b": "xlstm_1_3b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "starcoder2-7b": "starcoder2_7b",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "llama2-70b": "llama2_70b",
}

ASSIGNED_ARCHS = tuple(k for k in _ARCH_MODULES if k != "llama2-70b")

_cache: Dict[str, ModelConfig] = {}


def get_config(arch: str) -> ModelConfig:
    if arch not in _cache:
        if arch not in _ARCH_MODULES:
            raise KeyError(
                f"unknown arch {arch!r}; available: {sorted(_ARCH_MODULES)}"
            )
        mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch]}")
        _cache[arch] = mod.CONFIG
    return _cache[arch]


def list_archs(include_extra: bool = False) -> List[str]:
    return list(_ARCH_MODULES) if include_extra else list(ASSIGNED_ARCHS)


def get_shape(name: str) -> InputShape:
    return INPUT_SHAPES[name]
