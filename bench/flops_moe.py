"""Operations and bytes of a DeepSeek-V2 decode step (latent attention,
leading dense layers, then DeepSeekMoE layers), from the widths in a
model's configuration file. A step is the absorbed form the program
serves: the latent up-projection's key half folded into the query, its
value half applied after the weighted sum, so attention reads one cache row
of ``kv_lora_rank + qk_rope_head_dim`` values per key and layer.

FLOPs count 2 per multiply-add of every weight matrix a token passes
through (the router, the shared experts and only its top-k routed experts;
the output head included, the embedding lookup not) plus the attention
score and value products over the keys it attends to. Bytes are the least
one step reads and writes at batch 1: every matrix outside the routed
experts once, the top-k routed experts of each MoE layer (distinct at
batch 1), the router in float32, the head, one embedding row, norm scales
in float32, and the live cache rows read plus the new one written."""

from __future__ import annotations


def _w(m: dict):
    return (m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"])


def _layers(m: dict):
    """(dense layers, MoE layers)."""
    k = m["first_k_dense_replace"]
    return k, m["num_hidden_layers"] - k


def cache_row(m: dict) -> int:
    """Values cached per position per layer."""
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_params(m: dict) -> int:
    """wq, wkv_a, wkv_b and wo of one layer."""
    L, D, H, nope, rope, vd, r = _w(m)
    return D * H * (nope + rope) + D * (r + rope) + r * H * (nope + vd) + H * vd * D


def _mlp(m: dict, width: int) -> int:
    return 3 * m["hidden_size"] * width


def resident_params(m: dict) -> int:
    """Matrices a step reads whole: attention, dense MLPs, shared experts,
    the head and one embedding row (the router is counted apart)."""
    k, n_moe = _layers(m)
    shared = _mlp(m, m["n_shared_experts"] * m["moe_intermediate_size"])
    return (m["num_hidden_layers"] * attention_params(m) + k * _mlp(m, m["intermediate_size"])
            + n_moe * shared + m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


def routed_params(m: dict) -> int:
    """The top-k routed experts of every MoE layer."""
    k, n_moe = _layers(m)
    return n_moe * m["num_experts_per_tok"] * _mlp(m, m["moe_intermediate_size"])


def decode_flops(m: dict, n_keys: int) -> int:
    """One decode step whose token attends to ``n_keys`` keys (itself too)."""
    L, D, H, nope, rope, vd, r = _w(m)
    k, n_moe = _layers(m)
    router = n_moe * D * m["n_routed_experts"]
    absorbed = L * H * r * (nope + vd)  # query into the latent, latent out to values
    attn = L * H * (cache_row(m) + r) * n_keys  # scores over rows, weighted latent sum
    mats = resident_params(m) - D + routed_params(m) + router - L * r * H * (nope + vd)
    return 2 * (mats + absorbed + attn)


def decode_bytes(m: dict, n_keys: int) -> int:
    """Least bytes of one decode step at batch 1 (bf16 matrices and cache)."""
    L, D, H, nope, rope, vd, r = _w(m)
    k, n_moe = _layers(m)
    norms = (2 * L + 1) * D + L * r
    router = n_moe * D * m["n_routed_experts"]
    return (2 * (resident_params(m) + routed_params(m)) + 4 * (router + norms)
            + 2 * L * cache_row(m) * (n_keys + 1))
