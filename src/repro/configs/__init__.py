"""Architecture config registry.

``get_config(arch_id)`` returns the full assigned config; ``get_smoke_config``
returns a CPU-sized reduced config of the same family.
"""
from __future__ import annotations

from typing import Dict, Tuple

from repro.configs.base import (EncoderConfig, ModelConfig, MoEConfig,
                                RGLRUConfig, RopeConfig, SSMConfig, reduced)
from repro.configs.shapes import (ALL_SHAPES, DECODE_32K, LONG_500K,
                                  PREFILL_32K, SHAPES, TRAIN_4K, ShapeSpec,
                                  shapes_for)

from repro.configs import (granite_moe_1b_a400m, internvl2_1b, mamba2_13b,
                           mellum2_12b_a25b, phi3_medium_14b, qwen15_110b,
                           qwen2_15b, qwen25_05b, qwen25_15b, qwen3_14b,
                           qwen3_moe_235b_a22b, recurrentgemma_9b,
                           whisper_tiny)

# The ten assigned architectures (exact ids from the assignment table).
ASSIGNED: Dict[str, ModelConfig] = {
    "qwen1.5-110b": qwen15_110b.CONFIG,
    "phi3-medium-14b": phi3_medium_14b.CONFIG,
    "qwen3-14b": qwen3_14b.CONFIG,
    "qwen2-1.5b": qwen2_15b.CONFIG,
    "internvl2-1b": internvl2_1b.CONFIG,
    "recurrentgemma-9b": recurrentgemma_9b.CONFIG,
    "whisper-tiny": whisper_tiny.CONFIG,
    "qwen3-moe-235b-a22b": qwen3_moe_235b_a22b.CONFIG,
    "granite-moe-1b-a400m": granite_moe_1b_a400m.CONFIG,
    "mamba2-1.3b": mamba2_13b.CONFIG,
}

# The paper's own models (used by the reproduction benchmarks).
PAPER_MODELS: Dict[str, ModelConfig] = {
    "qwen2.5-0.5b": qwen25_05b.CONFIG,
    "qwen2.5-1.5b": qwen25_15b.CONFIG,
}

# Models the chip benchmark serves beyond the paper's (``bench/``).
BENCH_MODELS: Dict[str, ModelConfig] = {
    "mellum2-12b-a2.5b": mellum2_12b_a25b.CONFIG,
}

REGISTRY: Dict[str, ModelConfig] = {**ASSIGNED, **PAPER_MODELS,
                                    **BENCH_MODELS}


def get_config(arch: str) -> ModelConfig:
    if arch not in REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; available: {sorted(REGISTRY)}")
    return REGISTRY[arch]


def get_smoke_config(arch: str, **kw) -> ModelConfig:
    return reduced(get_config(arch), **kw)


def dryrun_cells() -> Tuple[Tuple[ModelConfig, ShapeSpec], ...]:
    """Every (assigned arch × applicable shape) pair for the dry-run."""
    cells = []
    for cfg in ASSIGNED.values():
        for shape in shapes_for(cfg.family):
            cells.append((cfg, shape))
    return tuple(cells)


__all__ = [
    "ASSIGNED", "PAPER_MODELS", "BENCH_MODELS", "REGISTRY", "ModelConfig",
    "MoEConfig", "RopeConfig", "SSMConfig", "RGLRUConfig", "EncoderConfig",
    "ShapeSpec", "SHAPES",
    "ALL_SHAPES", "TRAIN_4K", "PREFILL_32K", "DECODE_32K", "LONG_500K",
    "get_config", "get_smoke_config", "dryrun_cells", "shapes_for", "reduced",
]
