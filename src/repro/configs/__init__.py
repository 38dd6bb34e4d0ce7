"""Architecture registry: ``get_config("<arch-id>")`` / ``--arch <id>``.

The 10 assigned architectures plus the paper's own evaluation models.
``get_config(name, smoke=True)`` returns the reduced same-family config used
by CPU smoke tests; full configs are exercised only via the dry-run.
"""
from __future__ import annotations

import importlib

from repro.configs.base import (  # noqa: F401
    ModelConfig,
    YarnScaling,
    LayerGroup,
    layer_groups,
    input_specs,
    shape_applicable,
    SHAPES,
)

_MODULES = {
    # 10 assigned architectures
    "qwen3-1.7b": "qwen3_1_7b",
    "mistral-large-123b": "mistral_large_123b",
    "qwen2.5-3b": "qwen2_5_3b",
    "nemotron-4-15b": "nemotron_4_15b",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "phi-3-vision-4.2b": "phi_3_vision_4_2b",
    "whisper-medium": "whisper_medium",
    "dbrx-132b": "dbrx_132b",
    "deepseek-v3-671b": "deepseek_v3_671b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    # the paper's own evaluation models
    "llama3-8b": "llama3_8b",
    "qwen3-4b": "qwen3_4b",
    # the edge-sized MLA + expert model the benchmark serves
    "deepseek-v2-lite": "deepseek_v2_lite",
}

ASSIGNED = tuple(list(_MODULES)[:10])
PAPER_MODELS = ("llama3-8b", "qwen3-4b")
ALL_ARCHS = tuple(_MODULES)


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.SMOKE if smoke else mod.CONFIG
