"""Operations and bytes of a dense decoder, from the widths in a model's
configuration file. Model FLOPs count 2 per multiply-add of every weight
matrix a token passes through (the output head included, the embedding
lookup not) plus the attention score and value products over the keys the
token attends to; recomputation and padding do not count."""

from __future__ import annotations


def _dims(m: dict):
    return (m["num_hidden_layers"], m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
            m["vocab_size"])


def layer_matrix_params(m: dict) -> int:
    L, D, H, K, hd, F, V = _dims(m)
    return D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F


def params(m: dict) -> int:
    """Every parameter the model stores (biases and norm scales included)."""
    L, D, H, K, hd, F, V = _dims(m)
    per_layer = layer_matrix_params(m)
    if m["attention_bias"]:
        per_layer += H * hd + 2 * K * hd
    if m["qk_norm"]:
        per_layer += 2 * hd
    norms = 0 if m["norm"] == "layernorm_np" else D
    per_layer += 2 * norms
    head = 0 if m["tie_word_embeddings"] else V * D
    return V * D + head + L * per_layer + norms


def weight_bytes(m: dict) -> int:
    """Bytes one forward step reads of the weights: matrices and biases in
    the served dtype (2 bytes), norm scales in float32, the head once (a
    tied head is the embedding), and one embedding row."""
    L, D, H, K, hd, F, V = _dims(m)
    mats = L * layer_matrix_params(m) + V * D
    if m["attention_bias"]:
        mats += L * (H * hd + 2 * K * hd)
    norms = 0 if m["norm"] == "layernorm_np" else (2 * L + 1) * D
    if m["qk_norm"]:
        norms += 2 * L * hd
    return 2 * (mats + D) + 4 * norms


def kv_bytes_per_token(m: dict) -> int:
    L, D, H, K, hd, F, V = _dims(m)
    return 2 * L * K * hd * 2  # K and V, bf16


def matmul_flops_per_token(m: dict) -> int:
    L, D, H, K, hd, F, V = _dims(m)
    return 2 * (L * layer_matrix_params(m) + V * D)


def attention_flops(m: dict, n_keys: int) -> int:
    """Score and value products of one query token over ``n_keys`` keys."""
    L, D, H, K, hd, F, V = _dims(m)
    return 4 * L * H * hd * n_keys


def prefill_flops(m: dict, S: int) -> int:
    """Causal prefill of S tokens (token i attends to i + 1 keys)."""
    return S * matmul_flops_per_token(m) + attention_flops(m, S * (S + 1) // 2)


def decode_flops(m: dict, n_keys: int) -> int:
    """One decode step whose token attends to ``n_keys`` keys (itself too)."""
    return matmul_flops_per_token(m) + attention_flops(m, n_keys)


def decode_bytes(m: dict, n_keys: int) -> int:
    """Least bytes of one decode step: the weights, the live keys and values
    read, and the new token's written."""
    return weight_bytes(m) + kv_bytes_per_token(m) * (n_keys + 1)


def generate_flops(m: dict, S: int, n_out: int) -> int:
    """A generate call: prefill of S tokens, then n_out - 1 decode steps
    (the first token comes from the prefill; the last is not fed back)."""
    return prefill_flops(m, S) + sum(decode_flops(m, S + j + 1) for j in range(n_out - 1))
