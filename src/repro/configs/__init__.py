"""Architecture registry — the ``--arch <id>`` lookup.

Eleven architectures (one module each, exact published configs) +
``forge-125m`` (a GPT-2-class config for the paper-scale benchmarks and
the end-to-end training example).
"""
from __future__ import annotations

from typing import Dict, List

from .base import ModelConfig
from . import (
    deepseek_7b,
    deepseek_v2_lite,
    kimi_k2_1t_a32b,
    phi3_mini_38b,
    phi35_moe_42b_a66b,
    qwen15_32b,
    qwen2_vl_72b,
    qwen25_14b,
    recurrentgemma_2b,
    seamless_m4t_large_v2,
    xlstm_350m,
)
from .shapes import (
    SHAPES,
    SUBQUADRATIC,
    ShapeSpec,
    cache_specs,
    input_specs,
    params_specs,
    shape_applicable,
)

_MODULES = [
    seamless_m4t_large_v2,
    kimi_k2_1t_a32b,
    phi35_moe_42b_a66b,
    qwen15_32b,
    phi3_mini_38b,
    deepseek_7b,
    deepseek_v2_lite,
    qwen25_14b,
    recurrentgemma_2b,
    xlstm_350m,
    qwen2_vl_72b,
]

REGISTRY: Dict[str, object] = {m.ARCH_ID: m for m in _MODULES}
ARCH_IDS: List[str] = list(REGISTRY)


def forge_125m() -> ModelConfig:
    """GPT-2-class reference config (paper's smallest model family)."""
    return ModelConfig(
        name="forge-125m",
        family="dense",
        n_layers=12,
        d_model=768,
        n_heads=12,
        n_kv_heads=12,
        d_ff=3072,
        vocab=50257,
        ffn="gelu",
        ffn_bias=True,
        norm="layernorm",
        tie_embeddings=True,
        source="[GPT-2 125M layout]",
    )


def get_config(arch_id: str, smoke: bool = False) -> ModelConfig:
    if arch_id == "forge-125m":
        cfg = forge_125m()
        return cfg.with_(
            name=cfg.name + "-smoke", n_layers=2, d_model=64, n_heads=4,
            n_kv_heads=4, d_ff=128, vocab=512, remat=False,
        ) if smoke else cfg
    mod = REGISTRY.get(arch_id)
    if mod is None:
        raise KeyError(
            f"unknown arch {arch_id!r}; available: {ARCH_IDS + ['forge-125m']}"
        )
    return mod.smoke_config() if smoke else mod.config()


__all__ = [
    "ModelConfig",
    "REGISTRY",
    "ARCH_IDS",
    "get_config",
    "forge_125m",
    "SHAPES",
    "SUBQUADRATIC",
    "ShapeSpec",
    "cache_specs",
    "input_specs",
    "params_specs",
    "shape_applicable",
]
