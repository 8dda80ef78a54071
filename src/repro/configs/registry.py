"""Architecture registry: ``--arch <id>`` resolution for launcher/tests/benches.

Every assigned architecture (plus the paper's own llama_moe_4_16) registers a
FULL config and a reduced SMOKE config of the same structural family.
"""
from __future__ import annotations

import importlib

from repro.configs.base import SHAPES, ModelConfig, ShapeConfig

_MODULES = {
    "xlstm-1.3b": "xlstm_1_3b",
    "starcoder2-3b": "starcoder2_3b",
    "granite-8b": "granite_8b",
    "qwen2-7b": "qwen2_7b",
    "gemma3-27b": "gemma3_27b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "granite-moe-3b-a800m": "granite_moe_3b_a800m",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "zamba2-1.2b": "zamba2_1_2b",
    "llama-3.2-vision-90b": "llama_3_2_vision_90b",
    "whisper-base": "whisper_base",
    "llama_moe_4_16": "llama_moe_4_16",
}

ASSIGNED = [a for a in _MODULES if a != "llama_moe_4_16"]

# long_500k needs sub-quadratic attention: run only for SSM/hybrid families
# (constant or chunk-local state); full-attention archs skip (DESIGN.md §5).
LONG_CONTEXT_OK = {"xlstm-1.3b", "zamba2-1.2b"}


def list_archs() -> list[str]:
    return list(_MODULES)


def get_config(name: str, smoke: bool = False) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
    return mod.SMOKE if smoke else mod.CONFIG


def shape_cells(name: str) -> list[ShapeConfig]:
    """The (arch x shape) cells this architecture runs in the dry-run."""
    cells = [SHAPES["train_4k"], SHAPES["prefill_32k"], SHAPES["decode_32k"]]
    if name in LONG_CONTEXT_OK:
        cells.append(SHAPES["long_500k"])
    return cells


def all_cells() -> list[tuple[str, ShapeConfig]]:
    out = []
    for a in _MODULES:
        for s in shape_cells(a):
            out.append((a, s))
    return out
