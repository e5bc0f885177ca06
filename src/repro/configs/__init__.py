"""Assigned architecture configs (exact hyperparameters from the assignment).

Every architecture is selectable via ``--arch <id>`` in the launchers and is
simultaneously a DSE workload for the Lumina core
(``repro.perfmodel.workload.from_arch``).
"""
from __future__ import annotations

from typing import Dict

from repro.configs.base import ArchConfig, ShapeConfig, SHAPES, FULL_ATTENTION_SKIP

from repro.configs.codeqwen15_7b import CONFIG as codeqwen15_7b
from repro.configs.mistral_nemo_12b import CONFIG as mistral_nemo_12b
from repro.configs.qwen25_14b import CONFIG as qwen25_14b
from repro.configs.llama32_1b import CONFIG as llama32_1b
from repro.configs.qwen2_moe_a27b import CONFIG as qwen2_moe_a27b
from repro.configs.arctic_480b import CONFIG as arctic_480b
from repro.configs.jamba15_large_398b import CONFIG as jamba15_large_398b
from repro.configs.internvl2_2b import CONFIG as internvl2_2b
from repro.configs.whisper_medium import CONFIG as whisper_medium
from repro.configs.rwkv6_7b import CONFIG as rwkv6_7b
from repro.configs.deepseek_v3 import CONFIG as deepseek_v3

ARCHS: Dict[str, ArchConfig] = {
    c.name: c for c in (
        codeqwen15_7b, mistral_nemo_12b, qwen25_14b, llama32_1b,
        qwen2_moe_a27b, arctic_480b, jamba15_large_398b, internvl2_2b,
        whisper_medium, rwkv6_7b, deepseek_v3,
    )
}


def get_arch(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def cells():
    """All (arch, shape) grid cells, with skip annotations."""
    out = []
    for a in ARCHS.values():
        for s in SHAPES.values():
            skip = s.name in a.skip_shapes
            out.append((a, s, skip))
    return out


__all__ = ["ArchConfig", "ShapeConfig", "SHAPES", "ARCHS", "get_arch",
           "cells", "FULL_ATTENTION_SKIP"]
