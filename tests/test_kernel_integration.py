"""The Pallas flash kernel must match the reference attention on a model's
own projected q/k/v."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.kernels import ops
from repro.models import attention as attn
from repro.models.rope import apply_rope, positions_for_rope


def test_model_forward_with_pallas_matches_jnp(rng_key):
    cfg = registry.get_smoke("qwen2.5-3b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    p = attn.attn_init(cfg, rng_key)
    B, S = 1, 256  # S % 128 == 0: whole kernel blocks
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)
    q, k, v = attn._project_qkv(cfg, p, x)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    a = np.asarray(attn.naive_attention(q, k, v, causal=True), np.float32)
    b = np.asarray(ops.flash_attention_op(q, k, v, causal=True), np.float32)
    rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
    assert rel < 2e-3, rel
