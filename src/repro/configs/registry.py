"""Architecture registry: ``--arch <id>`` resolution.

Every assigned architecture is registered here together with its deployment
sharding profile. ``get(name)`` returns the full-size ModelConfig;
``get_smoke(name)`` returns the reduced CPU-smoke variant.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from repro.configs.base import (
    ModelConfig,
    ShapeConfig,
    SHAPES,
    ShardingProfile,
    reduce_for_smoke,
    supports_shape,
)

from repro.configs import (
    qwen3_4b,
    olmo_1b,
    nemotron_4_15b,
    qwen2_5_3b,
    rwkv6_3b,
    qwen2_vl_7b,
    kimi_k2_1t_a32b,
    granite_moe_1b_a400m,
    zamba2_2_7b,
    whisper_tiny,
    deepseek_v2_lite,
)

_MODULES = {
    "qwen3-4b": qwen3_4b,
    "olmo-1b": olmo_1b,
    "nemotron-4-15b": nemotron_4_15b,
    "qwen2.5-3b": qwen2_5_3b,
    "rwkv6-3b": rwkv6_3b,
    "qwen2-vl-7b": qwen2_vl_7b,
    "kimi-k2-1t-a32b": kimi_k2_1t_a32b,
    "granite-moe-1b-a400m": granite_moe_1b_a400m,
    "zamba2-2.7b": zamba2_2_7b,
    "whisper-tiny": whisper_tiny,
    "deepseek-v2-lite": deepseek_v2_lite,
}

ARCH_NAMES: List[str] = list(_MODULES)


def get(name: str) -> ModelConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; choose from {ARCH_NAMES}")
    return _MODULES[name].CONFIG


def get_sharding(name: str, kind: str = "") -> ShardingProfile:
    """Deployment profile; per-shape-kind overrides via SHARDING_<KIND>
    module attrs (e.g. olmo's train profile drops TP, its serving profile
    keeps it — batch 32 can't shard 256 ways)."""
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; choose from {ARCH_NAMES}")
    mod = _MODULES[name]
    if kind:
        return getattr(mod, f"SHARDING_{kind.upper()}", mod.SHARDING)
    return mod.SHARDING


def get_smoke(name: str) -> ModelConfig:
    return reduce_for_smoke(get(name))


def all_cells() -> List[Tuple[str, str]]:
    """All (arch, shape) dry-run cells per the assignment rules."""
    cells = []
    for arch in ARCH_NAMES:
        cfg = get(arch)
        for shape_name, shape in SHAPES.items():
            if supports_shape(cfg, shape):
                cells.append((arch, shape_name))
    return cells


def shape(name: str) -> ShapeConfig:
    return SHAPES[name]
