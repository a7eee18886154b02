"""Configuration dataclasses for the repro framework.

Every architecture in the assigned pool is expressed as a ``ModelConfig``;
deployment-level concerns (sharding, pipeline stages, remat) live in
``ShardingProfile``; the four assigned input shapes are ``ShapeConfig``s.

Configs are frozen dataclasses so they can be hashed into jit caches and
serialized into checkpoints / experiment logs.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# Sub-configs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class MoEConfig:
    """Mixture-of-experts layer configuration (GShard/Megablox-style)."""

    num_experts: int
    experts_per_token: int  # top-k
    d_ff_expert: int
    capacity_factor: float = 1.25
    # "dense": GShard one-hot dispatch einsums (auto-partitioned by pjit).
    # "ep": expert-parallel shard_map + all_to_all + ragged_dot grouped matmul.
    mode: str = "dense"
    router_jitter: float = 0.0
    aux_loss_weight: float = 0.01
    # EP-path perf knobs (hillclimb; see EXPERIMENTS.md §Perf):
    a2a_dtype: str = "auto"  # auto=x dtype | bfloat16 | float8_e4m3fn
    dispatch_chunks: int = 1  # split tokens into chunks: buffers / chunks
    # DeepSeekMoE (arXiv:2405.04434): shared experts that every token passes
    # through, applied as one MLP of num_shared_experts * d_ff_expert
    num_shared_experts: int = 0
    norm_topk_prob: bool = True  # renormalise the top-k weights to sum to 1
    first_k_dense: int = 0  # leading layers with a dense MLP of width d_ff


@dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434).

    Each position caches a normed latent of ``kv_lora_rank`` values and one
    rotary key of ``qk_rope_head_dim`` shared by every head; per-head keys
    (``qk_nope_head_dim`` + the rotary part) and values (``v_head_dim``)
    are expanded from the latent. Queries are projected directly (no
    low-rank query projection, as DeepSeek-V2-Lite).
    """

    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_dim(self) -> int:
        """Values cached per position per layer: latent + rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim


@dataclass(frozen=True)
class YaRNConfig:
    """YaRN rotary scaling as DeepSeek-V2 defines it (``rope_scaling``)."""

    factor: float
    original_max_position_embeddings: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMConfig:
    """State-space / linear-attention configuration."""

    kind: str  # "rwkv6" | "mamba2"
    state_dim: int = 64  # per-head state width (d_state)
    head_dim: int = 64
    expand: int = 2  # mamba2 inner expansion (d_inner = expand * d_model)
    conv_dim: int = 4  # depthwise conv width (mamba2)
    chunk_size: int = 128  # chunked-scan block length (TPU-friendly)


@dataclass(frozen=True)
class EncoderConfig:
    """Encoder tower for enc-dec models (whisper)."""

    num_layers: int
    num_frames: int = 1500  # stub frontend emits this many frames
    frame_dim: int = 384


# ---------------------------------------------------------------------------
# Model config
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | ssm | vlm | hybrid | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int

    # --- attention features ---
    qk_norm: bool = False
    qkv_bias: bool = False
    attn_chunk: int = 2048  # flash block size (VMEM/temp-memory knob)
    rope_theta: float = 10_000.0
    rope_type: str = "rope"  # rope | mrope | none
    attn_logit_softcap: float = 0.0

    # --- mlp / norm features ---
    mlp_act: str = "swiglu"  # swiglu | squared_relu | gelu
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm | layernorm_np
    tie_embeddings: bool = False

    # --- optional subsystems ---
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None  # latent attention in place of GQA
    yarn: Optional[YaRNConfig] = None  # YaRN-scaled rotary frequencies
    ssm: Optional[SSMConfig] = None
    encoder: Optional[EncoderConfig] = None

    # --- hybrid layout ---
    # attn_every = 0 -> attention-free (pure SSM).
    # attn_every = 1 -> attention in every layer (pure transformer).
    # attn_every = k>1 -> one (shared) attention block after every k SSM layers.
    attn_every: int = 1
    shared_attention: bool = False

    # --- frontend stub (modality models; see input_specs) ---
    frontend: str = "none"  # none | patch_embed | audio_frames

    # --- numerics ---
    dtype: str = "bfloat16"
    param_dtype: str = "bfloat16"

    # --- provenance ---
    source: str = ""

    # ------------------------------------------------------------------
    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_attention_free(self) -> bool:
        return self.attn_every == 0

    @property
    def num_attn_layers(self) -> int:
        """How many attention applications exist (KV-cache slots)."""
        if self.attn_every == 0:
            return 0
        if self.encoder is not None:
            return self.num_layers  # decoder self-attn layers
        return self.num_layers // self.attn_every

    @property
    def supports_subquadratic_decode(self) -> bool:
        """long_500k eligibility: SSM / hybrid / linear-attention families."""
        return self.family in ("ssm", "hybrid")

    def param_count(self) -> int:
        """Analytic parameter count (matches models.init within ties)."""
        d, v = self.d_model, self.vocab_size
        n = v * d  # embedding
        if not self.tie_embeddings:
            n += v * d  # output head
        per_layer = 0
        # attention block
        if self.mla is not None:
            m, h = self.mla, self.num_heads
            attn = (d * h * m.qk_head_dim + d * m.cache_dim + m.kv_lora_rank
                    + m.kv_lora_rank * h * (m.qk_nope_head_dim + m.v_head_dim)
                    + h * m.v_head_dim * d)
        else:
            attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        if self.qkv_bias:
            attn += self.q_dim + 2 * self.kv_dim
        # mlp block
        mlp_mats = 3 if self.mlp_act == "swiglu" else 2
        first_dense = 0
        if self.moe is not None:
            e = self.moe
            mlp = e.num_experts * (mlp_mats * d * e.d_ff_expert) + d * e.num_experts
            mlp += e.num_shared_experts * mlp_mats * d * e.d_ff_expert
            first_dense = e.first_k_dense
        else:
            mlp = mlp_mats * d * self.d_ff
        if self.ssm is not None and self.ssm.kind == "mamba2":
            # Zamba2-style: mamba2 mixer per layer, NO per-layer MLP; the MLP
            # lives inside the (shared) transformer block.
            s = self.ssm
            d_in = s.expand * d
            heads = d_in // s.head_dim
            ssm_p = (
                d * (2 * d_in + 2 * s.state_dim + heads)  # in_proj (z,x,B,C,dt)
                + s.conv_dim * (d_in + 2 * s.state_dim)  # depthwise conv
                + 3 * heads  # A_log, dt_bias, D
                + d_in  # pre-out norm
                + d_in * d  # out_proj
            )
            n += self.num_layers * ssm_p
            if self.attn_every > 0:
                n_attn = 1 if self.shared_attention else self.num_attn_layers
                n += n_attn * (attn + mlp)
        elif self.ssm is not None:  # rwkv6: time-mix + channel-mix per layer
            s = self.ssm
            heads = d // s.head_dim
            # r,k,v,g,w projections + out proj + decay lora + bonus + shift mixes
            ssm_p = 5 * d * d + d * d + 2 * heads * s.head_dim + 6 * d
            n += self.num_layers * (ssm_p + mlp)
        else:
            per_layer = attn + mlp
            n += self.num_layers * per_layer
            n += first_dense * (mlp_mats * d * self.d_ff - mlp)
        if self.encoder is not None:
            enc_attn = 4 * d * d
            enc_mlp = mlp_mats * d * self.d_ff
            cross = 4 * d * d
            n += self.encoder.num_layers * (enc_attn + enc_mlp)
            n += self.num_layers * cross  # decoder cross-attention
        return n

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts)."""
        if self.moe is None:
            return self.param_count()
        e = self.moe
        mlp_mats = 3 if self.mlp_act == "swiglu" else 2
        full_experts = e.num_experts * (mlp_mats * self.d_model * e.d_ff_expert)
        active_experts = e.experts_per_token * (mlp_mats * self.d_model * e.d_ff_expert)
        moe_layers = self.num_layers - e.first_k_dense
        return self.param_count() - moe_layers * (full_experts - active_experts)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, default=str)


# ---------------------------------------------------------------------------
# Shapes
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # train | prefill | decode


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


def supports_shape(model: ModelConfig, shape: ShapeConfig) -> bool:
    """Assignment rules: long_500k only for sub-quadratic archs."""
    if shape.name == "long_500k" and not model.supports_subquadratic_decode:
        return False
    return True


# ---------------------------------------------------------------------------
# Sharding / deployment profile
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardingProfile:
    """How a model is laid out on the mesh.

    Axis names refer to mesh axes ("pod", "data", "model"). ``fsdp_axes``
    shards parameters + optimizer state over those axes (ZeRO-3);
    ``tp_axis`` applies Megatron-pattern tensor parallelism; MoE expert
    weights shard over ``ep_axis`` when the MoE mode is "ep".
    """

    tp_axis: str = "model"
    fsdp_axes: Tuple[str, ...] = ()  # e.g. ("data",) or ("pod", "data")
    dp_axes: Tuple[str, ...] = ("data",)  # batch axes (pod is appended when present)
    ep_axis: str = "model"
    pipeline_axis: str = ""  # "" = no pipeline parallelism
    pipeline_stages: int = 1
    remat: str = "none"  # none | full | dots | offload
    optimizer_dtype: str = "float32"  # float32 | bfloat16 (1T-scale models)
    gradient_compression: str = "none"  # none | int8_ef
    kv_cache_dtype: str = "bfloat16"  # bfloat16 | int8
    # shard long KV caches over the TP axis by sequence when heads < tp size
    shard_kv_seq: bool = False
    # sequence parallelism: shard activations' seq dim over tp_axis (kills
    # within-head psums when heads % tp != 0; KV gathered once per layer)
    seq_parallel: bool = False
    # shard K/V projections over tp (disable when kv_dim/tp splits within
    # heads and causes per-block psums; replicating kv proj is cheap)
    shard_kv_proj: bool = True
    # use these mesh axes as ADDITIONAL data-parallel axes (e.g. ("model",)
    # for small models where TP is pure overhead; set tp_axis="" with it)
    extra_dp_axes: Tuple[str, ...] = ()


@dataclass(frozen=True)
class DeploymentConfig:
    """Full deployment = model + sharding + runtime knobs."""

    model: ModelConfig
    sharding: ShardingProfile = field(default_factory=ShardingProfile)
    max_decode_steps: int = 64
    microbatch: int = 0  # 0 = no gradient accumulation


# ---------------------------------------------------------------------------
# Reduced configs for smoke tests
# ---------------------------------------------------------------------------


def reduce_for_smoke(cfg: ModelConfig) -> ModelConfig:
    """Shrink a full config to CPU-smoke scale, preserving the family shape."""
    kw: Dict[str, Any] = dict(
        num_layers=min(cfg.num_layers, 2),
        d_model=128,
        num_heads=4,
        num_kv_heads=min(cfg.num_kv_heads, 4) if cfg.num_kv_heads < cfg.num_heads else 4,
        head_dim=32,
        d_ff=256,
        vocab_size=512,
    )
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(
            cfg.moe, num_experts=4, experts_per_token=2, d_ff_expert=64
        )
    if cfg.mla is not None:
        kw["mla"] = dataclasses.replace(
            cfg.mla, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16,
        )
    if cfg.ssm is not None:
        kw["ssm"] = dataclasses.replace(
            cfg.ssm, state_dim=16, head_dim=32, chunk_size=16
        )
    if cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(
            cfg.encoder, num_layers=2, num_frames=16, frame_dim=128
        )
    if cfg.attn_every > 1:
        kw["num_layers"] = 4
        kw["attn_every"] = 2
    return dataclasses.replace(cfg, name=cfg.name + "-smoke", **kw)
