"""deepseek-v2-lite: latent attention, shared and routed experts.

[hf:deepseek-ai/DeepSeek-V2-Lite config.json; equations from
arXiv:2405.04434] — 27L d_model=2048 16H, MLA (no q_lora; kv_lora_rank
512, qk_nope 128 + qk_rope 64, v 128), layer 0 a dense SwiGLU MLP of
10944, layers 1-26 DeepSeekMoE: 64 routed experts of 1408, top-6 by
softmax (greedy, routed_scaling_factor 1: the only router the model code
has), weights not renormalised, 2 shared experts; vocab=102400,
untied head, RMSNorm eps 1e-6, YaRN (factor 40 over 4096 positions).
~15.7B total / ~2.4B active params.

``head_dim`` fills a key config.json lacks (= qk_nope_head_dim =
v_head_dim); the MLA widths live in ``mla``. ``moe.mode`` is not read: an
MLA stack serves its experts on ``moe_forward_dropless`` (``models/lm``).
"""

from repro.configs.base import (
    MLAConfig,
    ModelConfig,
    MoEConfig,
    ShardingProfile,
    YaRNConfig,
)

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,
    d_ff=10_944,  # layer 0's dense MLP
    vocab_size=102_400,
    mlp_act="swiglu",
    norm_type="rmsnorm",
    rope_theta=10_000.0,
    tie_embeddings=False,
    moe=MoEConfig(
        num_experts=64,
        experts_per_token=6,
        d_ff_expert=1408,
        num_shared_experts=2,
        norm_topk_prob=False,
        first_k_dense=1,
    ),
    mla=MLAConfig(
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
    ),
    yarn=YaRNConfig(
        factor=40.0,
        original_max_position_embeddings=4096,
        beta_fast=32.0,
        beta_slow=1.0,
        mscale=0.707,
        mscale_all_dim=0.707,
    ),
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)

SHARDING = ShardingProfile(
    tp_axis="model",
    fsdp_axes=("data",),
    ep_axis="model",
    remat="full",
)
