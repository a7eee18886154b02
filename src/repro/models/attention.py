"""Attention: GQA projections (+qk-norm, +bias), flash-pattern causal
attention for train/prefill, and single-token decode attention.

Design notes (TPU adaptation):
  * The train/prefill path is a *chunked online-softmax* ("flash") attention
    written with a ``lax.scan`` over the lower-triangular block list, so the
    (S, S) score matrix is never materialized and — because every scanned
    block does identical work — the HLO while-loop trip count exactly equals
    the number of causal blocks (roofline.py multiplies body FLOPs by trip
    count, so causal FLOP accounting is exact: nq*(nq+1)/2 blocks).
  * The Pallas kernel (kernels/flash_attention.py) implements the same tiling
    for real TPUs, but no model path calls it yet; this jnp version is the
    path prefill runs, the dry-run's, and the numerical oracle.
  * Decode is a plain multiply-reduce over the KV cache (memory-bound; no
    benefit from chunking at batch sizes of interest), which the layer loop
    carries and writes one row of in place — kernels/decode_attention.py is
    the TPU kernel analogue.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import Params, dense_init, dtype_of, rms_norm, split_keys
from repro.models.rope import apply_rope, apply_rope_pairs, yarn_softmax_scale

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def attn_init(cfg, key, d_model: Optional[int] = None) -> Params:
    d = d_model or cfg.d_model
    dt = dtype_of(cfg.param_dtype)
    ks = split_keys(key, 4)
    p: Params = {
        "wq": dense_init(ks[0], (d, cfg.q_dim), dt),
        "wk": dense_init(ks[1], (d, cfg.kv_dim), dt),
        "wv": dense_init(ks[2], (d, cfg.kv_dim), dt),
        "wo": dense_init(ks[3], (cfg.q_dim, d), dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dt)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dt)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dt)
    if cfg.qk_norm:
        p["q_norm_scale"] = jnp.ones((cfg.head_dim,), jnp.float32)
        p["k_norm_scale"] = jnp.ones((cfg.head_dim,), jnp.float32)
    return p


def _project_qkv(cfg, p: Params, x: jnp.ndarray):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm_scale"])
        k = rms_norm(k, p["k_norm_scale"])
    return q, k, v


# ---------------------------------------------------------------------------
# Chunked causal flash attention (jnp / XLA path)
# ---------------------------------------------------------------------------


def flash_attention_jnp(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    chunk: int = 2048,
    causal: bool = True,
    logit_softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Online-softmax attention over (chunk x chunk) blocks.

    q, k: (B, S, Hq|Hkv, hd); v: (B, S, Hkv, vd); Hq % Hkv == 0 (GQA).
    Scores are scaled by ``scale`` (default hd**-0.5).
    Returns (B, S, Hq, vd). fp32 accumulators throughout.
    """
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    vd = v.shape[-1]
    G = Hq // Hkv
    C = min(chunk, S)
    pad = (-S) % C
    if pad:
        zq = jnp.zeros((B, pad, Hq, hd), q.dtype)
        zk = jnp.zeros((B, pad, Hkv, hd), k.dtype)
        zv = jnp.zeros((B, pad, Hkv, vd), v.dtype)
        q = jnp.concatenate([q, zq], axis=1)
        k = jnp.concatenate([k, zk], axis=1)
        v = jnp.concatenate([v, zv], axis=1)
    Sp = S + pad
    n = Sp // C
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)

    qc = q.reshape(B, n, C, Hkv, G, hd)
    kc = k.reshape(B, n, C, Hkv, hd)
    vc = v.reshape(B, n, C, Hkv, vd)

    if causal:
        pairs = [(i, j) for i in range(n) for j in range(i + 1)]
    else:
        pairs = [(i, j) for i in range(n) for j in range(n)]
    qi = jnp.array([p[0] for p in pairs], jnp.int32)
    kj = jnp.array([p[1] for p in pairs], jnp.int32)

    # block-local masks
    row = jnp.arange(C)[:, None]
    col = jnp.arange(C)[None, :]
    tri = (col > row).astype(jnp.float32) * NEG_INF  # (C, C) intra-block causal

    m0 = jnp.full((n, B, Hkv, G, C), NEG_INF, jnp.float32)
    l0 = jnp.zeros((n, B, Hkv, G, C), jnp.float32)
    a0 = jnp.zeros((n, B, Hkv, G, C, vd), jnp.float32)

    def body(carry, idx):
        m, l, acc = carry
        i, j = idx
        qb = jax.lax.dynamic_index_in_dim(qc, i, axis=1, keepdims=False)
        kb = jax.lax.dynamic_index_in_dim(kc, j, axis=1, keepdims=False)
        vb = jax.lax.dynamic_index_in_dim(vc, j, axis=1, keepdims=False)
        # scores: (B, Hkv, G, Cq, Ck)
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qb.astype(jnp.float32), kb.astype(jnp.float32)
        )
        s = s * scale
        if logit_softcap > 0.0:
            s = logit_softcap * jnp.tanh(s / logit_softcap)
        if causal:
            s = s + jnp.where(i == j, tri, 0.0)
        # mask padded keys (global col index >= S)
        if pad:
            gcol = j * C + jnp.arange(C)
            s = s + jnp.where(gcol >= S, NEG_INF, 0.0)[None, None, None, None, :]
        m_old = jax.lax.dynamic_index_in_dim(m, i, axis=0, keepdims=False)
        l_old = jax.lax.dynamic_index_in_dim(l, i, axis=0, keepdims=False)
        a_old = jax.lax.dynamic_index_in_dim(acc, i, axis=0, keepdims=False)
        m_blk = jnp.max(s, axis=-1)  # (B, Hkv, G, Cq)
        m_new = jnp.maximum(m_old, m_blk)
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l_old * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32))
        a_new = a_old * alpha[..., None] + pv
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, i, axis=0)
        l = jax.lax.dynamic_update_index_in_dim(l, l_new, i, axis=0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, a_new, i, axis=0)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (qi, kj))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (n, B, Hkv, G, C, vd) -> (B, S, Hq, vd)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sp, Hq, vd)
    return out[:, :S].astype(q.dtype)


def naive_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    logit_softcap: float = 0.0,
    kv_mask: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Reference softmax attention (materializes scores). Small shapes only.
    v may have its own head size; scores are scaled by ``scale`` (default
    hd**-0.5)."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    M = k.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(jnp.float32))
    if scale is None:
        s = s / jnp.sqrt(hd)
    else:
        s = s * scale
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    if causal:
        mask = jnp.arange(M)[None, :] > jnp.arange(S)[:, None]
        s = s + mask * NEG_INF
    if kv_mask is not None:  # (B, M) valid-key mask
        s = s + jnp.where(kv_mask, 0.0, NEG_INF)[:, None, None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, Hq, v.shape[-1]).astype(q.dtype)


def _one_query_attention(q, k, v, kv_mask, logit_softcap: float) -> jnp.ndarray:
    """``naive_attention`` of one query position (q: (B, 1, Hq, hd)) over
    k, v: (B, M, Hkv, hd) under kv_mask: (B, M), written as multiply-reduces
    instead of grouped dot products. XLA then reads K and V in their stored,
    position-major layout; a grouped dot wants them heads-major, and for a
    cache carried through a layer loop XLA lays out the whole cache so,
    copying it on the way into and out of the loop."""
    B, _, Hq, hd = q.shape
    Hkv = k.shape[2]
    qg = q.reshape(B, 1, Hkv, Hq // Hkv, hd).astype(jnp.float32)
    s = jnp.sum(qg * k.astype(jnp.float32)[:, :, :, None, :], axis=-1)  # (B, M, Hkv, G)
    s = s / jnp.sqrt(hd)
    if logit_softcap > 0.0:
        s = logit_softcap * jnp.tanh(s / logit_softcap)
    s = s + jnp.where(kv_mask, 0.0, NEG_INF)[:, :, None, None]
    p = jax.nn.softmax(s, axis=1)
    o = jnp.sum(p[..., None] * v.astype(jnp.float32)[:, :, :, None, :], axis=1)  # (B, Hkv, G, hd)
    return o.reshape(B, 1, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# Layer-level entry points
# ---------------------------------------------------------------------------


def attention_seq(
    cfg,
    p: Params,
    x: jnp.ndarray,
    cos,
    sin,
    *,
    causal: bool = True,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Full-sequence attention (train / prefill). Returns (out, (k, v)).
    Past 512 positions it runs in the chunked flash form, which never
    holds the (S, S) scores; shorter sequences use the reference form."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    if S > 512:
        o = flash_attention_jnp(
            q, k, v, causal=causal, logit_softcap=cfg.attn_logit_softcap,
            chunk=getattr(cfg, "attn_chunk", 2048),
        )
    else:
        o = naive_attention(q, k, v, causal=causal, logit_softcap=cfg.attn_logit_softcap)
    out = o.reshape(B, S, cfg.q_dim) @ p["wo"]
    return out, (k, v)


def attention_decode(
    cfg,
    p: Params,
    x: jnp.ndarray,
    cos,
    sin,
    cache_k: jnp.ndarray,
    cache_v: jnp.ndarray,
    layer,
    length: jnp.ndarray,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Single-token decode. x: (B, 1, D); cache_k/v: the whole stacked
    (L, B, M, Hkv, hd) K/V cache, of which row ``layer`` is this layer's;
    length: () number of valid cached positions. Writes the new token's K/V
    at ``(layer, :, length)`` and attends over positions [0, length].
    Returns (out (B,1,D), cache_k, cache_v). The caller carries the stacked
    cache through its layer loop, so the row is written in place (as
    ``mla_attention_decode`` does) and no buffer the size of the cache is
    copied.
    """
    B = x.shape[0]
    M = cache_k.shape[2]
    q, k, v = _project_qkv(cfg, p, x)  # (B, 1, H*, hd)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    pos = jnp.minimum(length, M - 1)
    at = (layer, 0, pos, 0, 0)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k[None].astype(cache_k.dtype), at)
    cache_v = jax.lax.dynamic_update_slice(cache_v, v[None].astype(cache_v.dtype), at)
    ck = jax.lax.dynamic_index_in_dim(cache_k, layer, axis=0, keepdims=False)
    cv = jax.lax.dynamic_index_in_dim(cache_v, layer, axis=0, keepdims=False)
    kv_mask = jnp.arange(M)[None, :] <= pos  # (1, M) -> broadcast over batch
    kv_mask = jnp.broadcast_to(kv_mask, (B, M))
    o = _one_query_attention(q, ck, cv, kv_mask, cfg.attn_logit_softcap)
    out = o.reshape(B, 1, cfg.q_dim) @ p["wo"]
    return out, cache_k, cache_v


def attention_extend(
    cfg,
    p: Params,
    x: jnp.ndarray,
    cos,
    sin,
    prefix_k: jnp.ndarray,
    prefix_v: jnp.ndarray,
    prefix_len: int,
) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Chunked-prefill continuation: the suffix attends over a prefilled
    prefix plus itself causally.

    x: (B, S, D) suffix activations; prefix_k/v: (B, Sp, Hkv, hd) cached
    post-RoPE prefix K/V (Sp >= prefix_len; positions past ``prefix_len``
    are page padding and are masked out); prefix_len: static int. The
    caller supplies cos/sin at positions offset by ``prefix_len`` — the
    suffix's RoPE phases continue where the prefix stopped.
    Returns (out (B, S, D), (k_suf, v_suf)).
    """
    B, S, _ = x.shape
    Sp = prefix_k.shape[1]
    q, k, v = _project_qkv(cfg, p, x)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    keys = jnp.concatenate([prefix_k.astype(q.dtype), k], axis=1)
    vals = jnp.concatenate([prefix_v.astype(q.dtype), v], axis=1)
    M = Sp + S
    Hkv = keys.shape[2]
    hd = cfg.head_dim
    G = cfg.num_heads // Hkv
    # per-query mask: prefix keys below prefix_len are always visible;
    # suffix keys are causal relative to the suffix row
    row = jnp.arange(S)[:, None]
    col = jnp.arange(M)[None, :]
    visible = jnp.where(col < Sp, col < prefix_len, (col - Sp) <= row)
    qg = q.reshape(B, S, Hkv, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, keys.astype(jnp.float32))
    s = s / jnp.sqrt(hd)
    if cfg.attn_logit_softcap > 0.0:
        s = cfg.attn_logit_softcap * jnp.tanh(s / cfg.attn_logit_softcap)
    s = jnp.where(visible[None, None, None], s, NEG_INF)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", pr, vals.astype(jnp.float32))
    out = o.reshape(B, S, cfg.q_dim).astype(q.dtype) @ p["wo"]
    return out, (k, v)


# ---------------------------------------------------------------------------
# Multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------
#
# A position's cache row is [c_kv (kv_lora_rank) after its RMSNorm, k_pe
# (qk_rope_head_dim) after RoPE]. Prefill expands per-head keys
# [k_nope, k_pe] and values from the latent through ``wkv_b``; decode
# attends in the latent space instead: the key half of ``wkv_b`` is folded
# into the query and the value half applied after the weighted sum, so a
# step reads the cache row and nothing per head.


def mla_init(cfg, key) -> Params:
    m, d, H = cfg.mla, cfg.d_model, cfg.num_heads
    dt = dtype_of(cfg.param_dtype)
    ks = split_keys(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * m.qk_head_dim), dt),
        "wkv_a": dense_init(ks[1], (d, m.cache_dim), dt),
        "kv_norm_scale": jnp.ones((m.kv_lora_rank,), jnp.float32),
        "wkv_b": dense_init(ks[2], (m.kv_lora_rank, H * (m.qk_nope_head_dim + m.v_head_dim)), dt),
        "wo": dense_init(ks[3], (H * m.v_head_dim, d), dt),
    }


def mla_cache_rows(cfg, p: Params, x: jnp.ndarray, cos, sin) -> jnp.ndarray:
    """x (B, S, D) -> cache rows (B, S, kv_lora_rank + qk_rope_head_dim)."""
    r = cfg.mla.kv_lora_rank
    kv = x @ p["wkv_a"]
    c_kv = rms_norm(kv[..., :r], p["kv_norm_scale"])
    k_pe = apply_rope_pairs(kv[..., r:], cos, sin)
    return jnp.concatenate([c_kv, k_pe], axis=-1)


def _mla_query(cfg, p: Params, x: jnp.ndarray, cos, sin):
    m = cfg.mla
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, m.qk_head_dim)
    q_pe = apply_rope_pairs(q[..., m.qk_nope_head_dim:], cos[:, :, None], sin[:, :, None])
    return q[..., : m.qk_nope_head_dim], q_pe


def _wkv_b(cfg, p: Params):
    """(kv_lora_rank, H, nope + v) view of the latent's up-projection."""
    m = cfg.mla
    return p["wkv_b"].reshape(m.kv_lora_rank, cfg.num_heads, m.qk_nope_head_dim + m.v_head_dim)


def mla_attention_seq(cfg, p: Params, x: jnp.ndarray, cos, sin):
    """Causal MLA over a whole sequence in the expanded form.
    Returns (out (B, S, D), cache rows (B, S, C))."""
    m = cfg.mla
    B, S, _ = x.shape
    with jax.named_scope("mla.attend"):
        rows = mla_cache_rows(cfg, p, x, cos, sin)
        q_nope, q_pe = _mla_query(cfg, p, x, cos, sin)
        kv = jnp.einsum("bsr,rhd->bshd", rows[..., : m.kv_lora_rank], _wkv_b(cfg, p))
        k_pe = jnp.broadcast_to(rows[:, :, None, m.kv_lora_rank:],
                                (B, S, cfg.num_heads, m.qk_rope_head_dim))
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        k = jnp.concatenate([kv[..., : m.qk_nope_head_dim], k_pe], axis=-1)
        v = kv[..., m.qk_nope_head_dim:]
        scale = yarn_softmax_scale(m.qk_head_dim, cfg.yarn)
        if S > 512:
            o = flash_attention_jnp(q, k, v, causal=True, scale=scale,
                                    chunk=getattr(cfg, "attn_chunk", 2048))
        else:
            o = naive_attention(q, k, v, causal=True, scale=scale)
        out = o.reshape(B, S, cfg.num_heads * m.v_head_dim) @ p["wo"]
    return out, rows


def mla_attention_decode(cfg, p: Params, x: jnp.ndarray, cos, sin, cache: jnp.ndarray,
                         layer, length: jnp.ndarray):
    """One token in the absorbed form. x: (B, 1, D); cache: the whole
    (L, B, M, C) latent cache, of which row ``layer`` is this layer's; the
    token's row is written at ``length`` and attends over [0, length].
    Returns (out (B, 1, D), cache)."""
    m = cfg.mla
    r = m.kv_lora_rank
    B = x.shape[0]
    M = cache.shape[2]
    with jax.named_scope("mla.attend"):
        row = mla_cache_rows(cfg, p, x, cos, sin)  # (B, 1, C)
        pos = jnp.minimum(length, M - 1)
        cache = jax.lax.dynamic_update_slice(
            cache, row[None].astype(cache.dtype), (layer, 0, pos, 0))
        lat = jax.lax.dynamic_index_in_dim(cache, layer, axis=0, keepdims=False)
        lat = lat.astype(jnp.float32)  # (B, M, C)
        q_nope, q_pe = _mla_query(cfg, p, x, cos, sin)
        w = _wkv_b(cfg, p)
        # keys k_nope = c_kv @ w_k, so q_nope . k_nope = (q_nope @ w_k^T) . c_kv
        q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w[..., : m.qk_nope_head_dim],
                           preferred_element_type=jnp.float32)
        qc = jnp.concatenate([q_lat, q_pe.astype(jnp.float32)], axis=-1)
        s = jnp.einsum("bshc,bmc->bhsm", qc, lat)
        s = s * yarn_softmax_scale(m.qk_head_dim, cfg.yarn)
        s = jnp.where((jnp.arange(M) <= pos)[None, None, None], s, NEG_INF)
        pr = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhsm,bmr->bshr", pr, lat[..., :r])
        # values v = c_kv @ w_v, applied after the weighted sum
        o = jnp.einsum("bshr,rhd->bshd", o_lat, w[..., m.qk_nope_head_dim:].astype(jnp.float32))
        out = o.reshape(B, 1, cfg.num_heads * m.v_head_dim).astype(x.dtype) @ p["wo"]
    return out, cache


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder)
# ---------------------------------------------------------------------------


def cross_attn_init(cfg, key) -> Params:
    d = cfg.d_model
    dt = dtype_of(cfg.param_dtype)
    ks = split_keys(key, 4)
    return {
        "wq": dense_init(ks[0], (d, cfg.q_dim), dt),
        "wk": dense_init(ks[1], (d, cfg.kv_dim), dt),
        "wv": dense_init(ks[2], (d, cfg.kv_dim), dt),
        "wo": dense_init(ks[3], (cfg.q_dim, d), dt),
    }


def cross_attention_kv(cfg, p: Params, enc_out: jnp.ndarray):
    """Precompute cross K/V from encoder output (done once per request)."""
    B, T, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"]).reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    return k, v


def cross_attention(cfg, p: Params, x: jnp.ndarray, ck: jnp.ndarray, cv: jnp.ndarray):
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    o = naive_attention(q, ck.astype(q.dtype), cv.astype(q.dtype), causal=False)
    return o.reshape(B, S, cfg.q_dim) @ p["wo"]
