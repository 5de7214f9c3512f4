"""Architecture registry: aggregates the per-arch config modules."""
from __future__ import annotations

import dataclasses

from repro.configs.base import ModelConfig
from repro.configs.deepseek_7b import DEEPSEEK_7B
from repro.configs.deepseek_moe_16b import DEEPSEEK_MOE_16B
from repro.configs.deepseek_v2_lite import DEEPSEEK_V2_LITE
from repro.configs.falcon_mamba_7b import FALCON_MAMBA_7B
from repro.configs.gemma2_27b import GEMMA2_27B
from repro.configs.llava_next_mistral_7b import LLAVA_NEXT_MISTRAL_7B
from repro.configs.minicpm_2b import MINICPM_2B
from repro.configs.qwen2_moe_a2_7b import QWEN2_MOE_A2_7B
from repro.configs.qwen3_0_6b import QWEN3_0_6B
from repro.configs.recurrentgemma_2b import RECURRENTGEMMA_2B
from repro.configs.whisper_tiny import WHISPER_TINY

ARCHS: dict[str, ModelConfig] = {
    c.name: c for c in (
        GEMMA2_27B, DEEPSEEK_7B, MINICPM_2B, QWEN3_0_6B, RECURRENTGEMMA_2B,
        WHISPER_TINY, LLAVA_NEXT_MISTRAL_7B, QWEN2_MOE_A2_7B, DEEPSEEK_MOE_16B,
        FALCON_MAMBA_7B, DEEPSEEK_V2_LITE,
    )
}

# (arch, shape) cells that are skipped, with the reason recorded here and in
# DESIGN.md §Arch-applicability. Everything else must dry-run.
SKIPS: dict[tuple[str, str], str] = {
    ("deepseek-7b", "long_500k"): "pure full attention (quadratic) — per assignment",
    ("minicpm-2b", "long_500k"): "pure full attention (quadratic) — per assignment",
    ("qwen3-0.6b", "long_500k"): "pure full attention (quadratic) — per assignment",
    ("whisper-tiny", "long_500k"): "enc-dec full attention; decoder max ctx 448 — per assignment",
    ("llava-next-mistral-7b", "long_500k"): "pure full attention (quadratic) — per assignment",
    ("qwen2-moe-a2.7b", "long_500k"): "pure full attention (quadratic) — per assignment",
    ("deepseek-moe-16b", "long_500k"): "pure full attention (quadratic) — per assignment",
    ("deepseek-v2-lite", "long_500k"): "pure full attention (quadratic) — per assignment",
}
# gemma2-27b long_500k RUNS: its local layers cap the KV cache at the 4096
# window and the global layers use a context-parallel (length-sharded) cache.
# recurrentgemma-2b / falcon-mamba-7b long_500k RUN: O(1) recurrent state.


def get_arch(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {sorted(ARCHS)}")
    return ARCHS[name]


def is_skipped(arch: str, shape: str) -> str | None:
    return SKIPS.get((arch, shape))


def all_cells(include_skipped: bool = False) -> list[tuple[str, str]]:
    from repro.configs.shapes import SHAPES
    cells = []
    for a in ARCHS:
        for s in SHAPES:
            if include_skipped or (a, s) not in SKIPS:
                cells.append((a, s))
    return cells
