"""Config registry: ``get_config("<arch-id>")`` and reduced smoke configs.

Arch ids are the assignment-table ids; ``mixtral-8x7b`` is the paper's own
evaluation model and is included in addition to the 10 assigned archs.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from repro.configs.base import (  # noqa: F401 (re-export)
    EncoderConfig,
    LayerGroup,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    ShapeConfig,
    SHAPES,
    SSMConfig,
    TRAIN_4K,
    VisionConfig,
    YarnConfig,
)

_MODULES = {
    "qwen2.5-3b": "qwen2_5_3b",
    "gemma2-27b": "gemma2_27b",
    "yi-6b": "yi_6b",
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "dbrx-132b": "dbrx_132b",
    "deepseek-moe-16b": "deepseek_moe_16b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "phi-3-vision-4.2b": "phi_3_vision_4_2b",
    "mamba2-1.3b": "mamba2_1_3b",
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "mixtral-8x7b": "mixtral_8x7b",
    "deepseek-v2-lite": "deepseek_v2_lite",
}

# mixtral is the paper's own model; deepseek-v2-lite the benchmark's latent one
ASSIGNED_ARCHS: List[str] = [a for a in _MODULES
                             if a not in ("mixtral-8x7b", "deepseek-v2-lite")]
ALL_ARCHS: List[str] = list(_MODULES)

_cache: Dict[str, ModelConfig] = {}


def get_config(name: str) -> ModelConfig:
    if name not in _cache:
        if name not in _MODULES:
            raise KeyError(f"unknown arch {name!r}; known: {sorted(_MODULES)}")
        import importlib

        mod = importlib.import_module(f"repro.configs.{_MODULES[name]}")
        _cache[name] = mod.CONFIG
    return _cache[name]


def tiny_config(name: str, *, seq_len: int = 64) -> ModelConfig:
    """A reduced same-family config for CPU smoke tests: same layer pattern
    shape (fewer repeats), tiny widths, tiny vocab. Exercises the identical
    code paths as the full config."""
    cfg = get_config(name)
    groups = tuple(
        dataclasses.replace(g, repeats=min(g.repeats, 2)) for g in cfg.layer_groups
    )
    n_layers = sum(g.n_layers for g in groups)
    moe = (
        dataclasses.replace(
            cfg.moe,
            num_experts=min(cfg.moe.num_experts, 4),
            top_k=min(cfg.moe.top_k, 2),
            num_shared_experts=min(cfg.moe.num_shared_experts, 1),
            d_expert=64,
        )
        if cfg.moe
        else None
    )
    ssm = (
        dataclasses.replace(cfg.ssm, d_state=16, head_dim=16, chunk_size=16)
        if cfg.ssm
        else None
    )
    enc = dataclasses.replace(cfg.encoder, n_layers=2, cross_attn_memory=32) if cfg.encoder else None
    vis = dataclasses.replace(cfg.vision, n_patches=8, d_patch=48) if cfg.vision else None
    mla = (MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16)
           if cfg.mla else None)
    return cfg.scaled(
        name=cfg.name + "-tiny",
        n_layers=n_layers,
        d_model=64,
        n_heads=4 if cfg.n_heads else 0,
        n_kv_heads=min(cfg.n_kv_heads, 2) if cfg.n_kv_heads else 0,
        head_dim=16,
        d_ff=128 if cfg.d_ff else 0,
        dense_d_ff=160 if cfg.dense_d_ff else 0,
        vocab=256,
        sliding_window=min(cfg.sliding_window, seq_len // 4) if cfg.sliding_window else 0,
        moe=moe,
        ssm=ssm,
        encoder=enc,
        vision=vis,
        mla=mla,
        layer_groups=groups,
    )
