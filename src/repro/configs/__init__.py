"""Architecture registry: the 10 assigned configs, Granite 4.0-H Micro, and
reduced smoke variants."""

from __future__ import annotations

import dataclasses
from typing import Dict, List

from repro.configs.base import (
    ALL_SHAPES,
    LayerSpec,
    ModelConfig,
    SHAPES_BY_NAME,
    ShapeSpec,
)

from repro.configs.jamba_v0_1_52b import CONFIG as JAMBA
from repro.configs.qwen2_vl_72b import CONFIG as QWEN2_VL
from repro.configs.mixtral_8x22b import CONFIG as MIXTRAL
from repro.configs.llama4_scout_17b_a16e import CONFIG as LLAMA4_SCOUT
from repro.configs.rwkv6_1_6b import CONFIG as RWKV6
from repro.configs.starcoder2_3b import CONFIG as STARCODER2
from repro.configs.qwen3_14b import CONFIG as QWEN3
from repro.configs.stablelm_3b import CONFIG as STABLELM
from repro.configs.gemma3_27b import CONFIG as GEMMA3
from repro.configs.whisper_medium import CONFIG as WHISPER
from repro.configs.granite_4_0_h_micro import CONFIG as GRANITE_H_MICRO

ARCHS: Dict[str, ModelConfig] = {
    c.name: c
    for c in [
        JAMBA,
        QWEN2_VL,
        MIXTRAL,
        LLAMA4_SCOUT,
        RWKV6,
        STARCODER2,
        QWEN3,
        STABLELM,
        GEMMA3,
        WHISPER,
        GRANITE_H_MICRO,
    ]
}


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(ARCHS)}")
    return ARCHS[name]


def shapes_for(cfg: ModelConfig) -> List[ShapeSpec]:
    """Applicable shape cells for an arch (system-spec skip rules)."""
    out = []
    for s in ALL_SHAPES:
        if s.name in cfg.skip_shapes:
            continue
        if s.name == "long_500k" and not (cfg.long_context_ok or cfg.sub_quadratic()):
            continue
        out.append(s)
    return out


def all_cells() -> List[tuple]:
    """Every (arch, shape) dry-run cell, with skips applied."""
    cells = []
    for name, cfg in ARCHS.items():
        for s in shapes_for(cfg):
            cells.append((name, s.name))
    return cells


def reduced_config(cfg: ModelConfig) -> ModelConfig:
    """Tiny same-family variant for CPU smoke tests: identical structure
    (pattern, attention flavors, MoE/SSM wiring), minimal widths."""
    head_dim = 16
    heads = 4
    ratio = max(1, cfg.num_heads // cfg.num_kv_heads)
    kv = max(1, heads // ratio)
    half = head_dim // 2
    mrope = (2, 3, 3) if cfg.rope == "mrope" else ()
    assert not mrope or sum(mrope) == half
    nblocks = min(2, cfg.num_blocks)
    ssm_heads = min(cfg.ssm_heads, 4)
    return dataclasses.replace(
        cfg,
        name=cfg.name + "-smoke",
        num_layers=len(cfg.pattern) * nblocks + len(cfg.tail_pattern),
        d_model=64,
        num_heads=heads,
        num_kv_heads=kv,
        d_ff=128,
        vocab_size=509,  # deliberately non-multiple: exercises vocab padding
        head_dim=head_dim,
        mrope_sections=mrope,
        num_experts=4 if cfg.num_experts else 0,
        top_k=min(cfg.top_k, 2),
        ssm_state_dim=8,
        ssm_heads=ssm_heads,
        ssm_head_dim=cfg.ssm_expand * 64 // ssm_heads if ssm_heads else cfg.ssm_head_dim,
        ssm_chunk=min(cfg.ssm_chunk, 8),
        rwkv_head_size=16,
        encoder_layers=2 if cfg.encoder_layers else 0,
        encoder_seq=32 if cfg.encoder_seq else 0,
        dtype="float32",
        param_dtype="float32",
        pattern=tuple(
            dataclasses.replace(s, window=min(s.window, 8) if s.window else 0)
            for s in cfg.pattern
        ),
        tail_pattern=tuple(
            dataclasses.replace(s, window=min(s.window, 8) if s.window else 0)
            for s in cfg.tail_pattern
        ),
    )
