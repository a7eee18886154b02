"""Per-arch smoke + prefill/decode equivalence (the core serving invariant)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import lm

ARCHS = registry.ARCH_NAMES


def _fp32(cfg):
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0)
        )
    return cfg


def _batches(cfg, key, B, S):
    tok = jax.random.randint(key, (B, S + 1), 0, cfg.vocab_size)
    if cfg.family == "audio":
        fr = jax.random.normal(key, (B, cfg.encoder.num_frames, cfg.d_model))
        return {"frames": fr, "tokens": tok}, tok
    return {"tokens": tok}, tok


@pytest.mark.parametrize("arch", ARCHS)
def test_forward_shapes_and_finite(arch, rng_key):
    cfg = registry.get_smoke(arch)
    p = lm.init_params(cfg, rng_key)
    B, S = 2, 10
    batch, tok = _batches(cfg, jax.random.PRNGKey(1), B, S)
    full = dict(batch)
    full["tokens"] = tok
    logits, aux, _ = lm.forward(cfg, p, full)
    assert logits.shape == (B, S + 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_cache_has_the_init_cache_layout(arch):
    """Prefill, and prefix extension where the cache takes a stored prefix,
    return exactly init_cache's tree, shapes and dtypes: decode's donated
    cache keeps one layout whichever program built it."""
    cfg = registry.get_smoke(arch)
    B, S, M, enc_len = 2, 6, 16, 0
    params = lm.abstract_params(cfg)
    batch = {"tokens": jnp.zeros((B, S), jnp.int32)}
    if cfg.family == "audio":
        enc_len = 12
        batch["frames"] = jnp.zeros((B, enc_len, cfg.d_model), jnp.float32)

    def layout(tree):
        return jax.tree.map(lambda a: (a.shape, a.dtype), tree)

    want = layout(lm.abstract_cache(cfg, B, M, enc_len=enc_len))
    _, cache = jax.eval_shape(lambda p: lm.prefill(cfg, p, batch, cache_len=M), params)
    assert layout(cache) == want
    if lm.prefix_refusal(cfg) is None:
        L, _, _, H, hd = want["kv_k"][0]
        pk = jax.ShapeDtypeStruct((L, B, 8, H, hd), want["kv_k"][1])
        _, cache = jax.eval_shape(
            lambda p, k, v: lm.prefill_extend(cfg, p, batch, k, v, 5, cache_len=M),
            params, pk, pk)
        assert layout(cache) == want


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_equivalence(arch, rng_key):
    """forward(S+1)[-1] == prefill(S) + decode_step(token S)."""
    cfg = _fp32(registry.get_smoke(arch))
    p = lm.init_params(cfg, rng_key)
    B, S = 2, 34  # multi-chunk for ssm (smoke chunk=16)
    batch, tok = _batches(cfg, jax.random.PRNGKey(2), B, S)
    full = dict(batch)
    full["tokens"] = tok
    logits_full, _, _ = lm.forward(cfg, p, full)
    pre = dict(batch)
    pre["tokens"] = tok[:, :S]
    _, cache = lm.prefill(cfg, p, pre, cache_len=S + 4)
    ld, cache2 = lm.decode_step(cfg, p, cache, tok[:, S : S + 1])
    a = np.asarray(logits_full[:, -1], np.float32)
    b = np.asarray(ld[:, 0], np.float32)
    rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
    assert rel < 5e-4, (arch, rel)
    assert int(cache2["length"]) == S + 1


@pytest.mark.parametrize(
    "arch", ["qwen3-4b", "olmo-1b", "qwen2.5-3b", "rwkv6-3b", "zamba2-2.7b", "whisper-tiny"]
)
def test_multi_step_decode_matches_forward(arch, rng_key):
    cfg = _fp32(registry.get_smoke(arch))
    p = lm.init_params(cfg, rng_key)
    B, S, extra = 1, 18, 3
    batch, tok = _batches(cfg, jax.random.PRNGKey(3), B, S + extra - 1)
    full = dict(batch)
    full["tokens"] = tok
    logits_full, _, _ = lm.forward(cfg, p, full)
    pre = dict(batch)
    pre["tokens"] = tok[:, :S]
    _, cache = lm.prefill(cfg, p, pre, cache_len=S + extra + 2)
    for i in range(extra):
        ld, cache = lm.decode_step(cfg, p, cache, tok[:, S + i : S + i + 1])
        a = np.asarray(logits_full[:, S + i], np.float32)
        b = np.asarray(ld[:, 0], np.float32)
        rel = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
        assert rel < 1e-3, (arch, i, rel)


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "olmo-1b"])
def test_decode_writes_cache_in_place(arch):
    """The decode program writes each layer's K/V row into the donated
    stacked cache: it copies no buffer of the cache's shape, and its
    temporaries stay under two K buffers (a scan-in/scan-out layer loop
    copies the cache three times and holds 3.5 K buffers)."""
    cfg = _fp32(registry.get_smoke(arch))
    cache = lm.abstract_cache(cfg, 1, 512)
    step = jax.jit(lambda p, c, t: lm.decode_step(cfg, p, c, t), donate_argnums=(1,))
    compiled = step.lower(
        lm.abstract_params(cfg), cache, jax.ShapeDtypeStruct((1, 1), jnp.int32)
    ).compile()
    kv = cache["kv_k"]
    stacked = "= f32[%s]" % ",".join(map(str, kv.shape))
    copies = [l for l in compiled.as_text().splitlines() if stacked in l and " copy(" in l]
    assert not copies, copies
    kv_bytes = kv.size * kv.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * kv_bytes


def test_vlm_embeds_path(rng_key):
    cfg = registry.get_smoke("qwen2-vl-7b")
    p = lm.init_params(cfg, rng_key)
    B, S = 2, 8
    emb = jax.random.normal(rng_key, (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S))
    logits, _, _ = lm.forward(cfg, p, {"embeds": emb, "positions": pos})
    assert logits.shape == (B, S, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


def test_mrope_positions_change_output(rng_key):
    """M-RoPE must actually use the 3D position streams."""
    cfg = registry.get_smoke("qwen2-vl-7b")
    p = lm.init_params(cfg, rng_key)
    B, S = 1, 8
    emb = jax.random.normal(rng_key, (B, S, cfg.d_model))
    pos1 = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, None], (3, B, S))
    pos2 = pos1.at[1].set(pos1[1] * 3)  # different height stream
    l1, _, _ = lm.forward(cfg, p, {"embeds": emb, "positions": pos1})
    l2, _, _ = lm.forward(cfg, p, {"embeds": emb, "positions": pos2})
    assert np.abs(np.asarray(l1 - l2, np.float32)).max() > 1e-4


def test_flash_vs_naive_attention_in_model(rng_key):
    """The chunked flash path (S>512) must match naive attention."""
    from repro.models import attention as attn

    cfg = registry.get_smoke("qwen2.5-3b")
    cfg = dataclasses.replace(cfg, dtype="float32", param_dtype="float32")
    p = attn.attn_init(cfg, rng_key)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 640, cfg.d_model))
    from repro.models.rope import apply_rope, positions_for_rope

    pos = jnp.broadcast_to(jnp.arange(640, dtype=jnp.int32)[None], (2, 640))
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)
    q, k, v = attn._project_qkv(cfg, p, x)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    o_flash = attn.flash_attention_jnp(q, k, v, chunk=cfg.attn_chunk)
    o_naive = attn.naive_attention(q, k, v)
    assert np.abs(np.asarray(o_flash - o_naive, np.float32)).max() < 1e-3
