"""The paper's own deployment: APC on a Minion-style Plan-Act agent.

The paper (§4.1) used GPT-4o as the large planner, LLaMa-3.1-8B as both the
small planner and the actor, and GPT-4o-mini for keyword extraction / cache
generation. In this framework the tiers are drawn from the assigned model zoo
(all open configs), preserving the size ordering:

    large planner   : nemotron-4-15b (largest dense) or kimi-k2 (MoE flagship)
    small planner   : olmo-1b
    actor           : qwen2.5-3b
    keyword/cachegen: olmo-1b (reduced)

Token prices for the $-cost model come straight from the paper's Table 8 so
benchmark dollar figures stay comparable with the paper.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict


@dataclass(frozen=True)
class TierPricing:
    """$ per million tokens (paper Table 8)."""

    input_per_m: float
    output_per_m: float


# Paper Table 8, verbatim.
PAPER_PRICES: Dict[str, TierPricing] = {
    "gpt-4o": TierPricing(2.50, 10.00),
    "gpt-4o-mini": TierPricing(0.15, 0.60),
    "claude-3.5-sonnet": TierPricing(3.00, 15.00),
    "llama-3.1-8b": TierPricing(0.18, 0.18),
    "llama-3.2-3b": TierPricing(0.06, 0.06),
    "qwen-2.5-7b": TierPricing(0.30, 0.30),
}


@dataclass(frozen=True)
class APCDeployment:
    """Which arch plays which APC role, and how each role is priced."""

    large_planner: str = "nemotron-4-15b"
    small_planner: str = "olmo-1b"
    actor: str = "qwen2.5-3b"
    keyword_extractor: str = "olmo-1b"
    # price table role -> Table 8 model (keeps $ comparable to the paper)
    pricing: Dict[str, str] = field(
        default_factory=lambda: {
            "large_planner": "gpt-4o",
            "small_planner": "llama-3.1-8b",
            "actor": "llama-3.1-8b",
            "keyword_extractor": "gpt-4o-mini",
            "cache_generator": "gpt-4o-mini",
        }
    )
    max_iterations: int = 10  # paper §4.1
    cache_capacity: int = 100  # paper Table 4 default
    fuzzy_matching: bool = False  # paper default: exact matching
    fuzzy_threshold: float = 0.8
    # repro.index: auto | brute | pallas | bucketed | device
    # ("device" keeps the embedding bank resident on the accelerator —
    # zero bank H2D per lookup; see docs/architecture.md)
    index_backend: str = "auto"


# DEFAULT does not fit one TPU v5e at published widths: nemotron-4-15b is
# 31.3 GB in bf16 against the chip's 16 GB of HBM. It needs a large planner
# split over several chips, which no served path builds yet.
DEFAULT = APCDeployment()

# The deployment that fits one v5e at published widths: qwen2.5-3b (6.79 GB
# bf16) plays actor and large planner, olmo-1b (2.35 GB) small planner and
# keyword extractor. Fuzzy plan-cache matching on the device-resident index.
ONE_CHIP = APCDeployment(
    large_planner="qwen2.5-3b",
    fuzzy_matching=True,
    index_backend="device",
)

# Flagship-scale variant: trillion-param MoE as the large planner.
FLAGSHIP = APCDeployment(large_planner="kimi-k2-1t-a32b")
