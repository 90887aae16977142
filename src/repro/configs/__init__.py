"""Architecture registry: one module per assigned architecture.

``get_config(arch_id, **overrides)`` returns the full-size ModelConfig;
``get_smoke_config(arch_id)`` returns the reduced same-family config used
by CPU smoke tests.  ``SHAPES`` defines the assigned input-shape set (same
for every LM-family arch, per the assignment).
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, List

ARCHS: List[str] = [
    "whisper_medium",
    "mamba2_1p3b",
    "qwen3_8b",
    "llama3_8b",
    "gemma_7b",
    "smollm_360m",
    "mixtral_8x7b",
    "llama4_maverick_400b_a17b",
    "llama32_vision_11b",
    "recurrentgemma_9b",
    "mellum2_12b",
]

# Canonical external ids (assignment sheet) → module names
ALIASES: Dict[str, str] = {
    "whisper-medium": "whisper_medium",
    "mamba2-1.3b": "mamba2_1p3b",
    "qwen3-8b": "qwen3_8b",
    "llama3-8b": "llama3_8b",
    "gemma-7b": "gemma_7b",
    "smollm-360m": "smollm_360m",
    "mixtral-8x7b": "mixtral_8x7b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "llama-3.2-vision-11b": "llama32_vision_11b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "mellum2-12b": "mellum2_12b",
}

SHAPES: Dict[str, Dict] = {
    "train_4k": {"seq_len": 4096, "global_batch": 256, "kind": "train"},
    "prefill_32k": {"seq_len": 32768, "global_batch": 32, "kind": "prefill"},
    "decode_32k": {"seq_len": 32768, "global_batch": 128, "kind": "decode"},
    "long_500k": {"seq_len": 524288, "global_batch": 1, "kind": "decode"},
}


def _module(arch: str):
    name = ALIASES.get(arch, arch).replace("-", "_")
    return importlib.import_module(f"repro.configs.{name}")


def get_config(arch: str, **overrides):
    cfg = _module(arch).config()
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def get_smoke_config(arch: str, **overrides):
    cfg = _module(arch).smoke_config()
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


def supports_shape(cfg, shape_name: str) -> bool:
    """long_500k needs sub-quadratic attention (DESIGN.md §4)."""
    if shape_name == "long_500k":
        return cfg.sub_quadratic
    return True


__all__ = ["ARCHS", "ALIASES", "SHAPES", "get_config", "get_smoke_config",
           "supports_shape"]
