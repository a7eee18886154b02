"""Unified language model: one init/forward/prefill/decode API for every
assigned architecture family (dense, moe, ssm, hybrid, vlm, audio enc-dec).

Layer stacks run under ``lax.scan`` with stacked per-layer parameters
(leading L axis) — production pattern: O(1) HLO size in depth, FSDP
all-gathers live inside the loop body (roofline.py multiplies while-body
costs by trip count, so accounting stays exact).

Caches are explicit pytrees (see ``init_cache``), so serving code jits
``decode_step`` with donated cache buffers. A latent-attention (MLA) model
(DeepSeek-V2: MLA with DeepSeekMoE experts, the only MLA stack served)
caches one ``(L, B, M, kv_lora_rank + qk_rope_head_dim)`` array
(``kv_latent``) in place of ``kv_k``/``kv_v``, and the routed (token,
expert) pairs per MoE layer and expert so far (``expert_tokens``,
``(L_moe, E)`` int32), summed on the device by prefill and each decode
step.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention as attn
from repro.models import mamba as mam
from repro.models import moe as moe_mod
from repro.models import rwkv as rwkv_mod
from repro.models.common import (
    Params,
    apply_norm,
    dense_init,
    dtype_of,
    mlp_forward,
    mlp_init,
    norm_param_init,
    split_keys,
)
from repro.models.rope import positions_for_rope, yarn_angles

Batch = Dict[str, jnp.ndarray]
Cache = Dict[str, jnp.ndarray]

# cache key of the routed (token, expert) pairs per MoE layer and expert
EXPERT_TOKENS = "expert_tokens"


@dataclass(frozen=True)
class ParallelCtx:
    """Mesh context threaded through forwards (None mesh = single device)."""

    mesh: Any = None
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    ep_axis: str = "model"
    remat: str = "none"
    seq_parallel: bool = False

    @property
    def batch_spec(self):
        if len(self.dp_axes) == 1:
            return self.dp_axes[0]
        return tuple(self.dp_axes)

    @property
    def seq_spec(self):
        return self.tp_axis if (self.seq_parallel and self.tp_axis) else None


def _constrain(x, ctx: Optional[ParallelCtx], spec):
    if ctx is None or ctx.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx.mesh, spec)
    )


def _maybe_remat(fn, ctx: Optional[ParallelCtx]):
    mode = ctx.remat if ctx is not None else "none"
    if mode == "none":
        return fn
    if mode == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)  # full


# ===========================================================================
# Parameter initialization
# ===========================================================================


def _norm_params(cfg, key_prefix: str) -> Params:
    out = {}
    base = norm_param_init(cfg, cfg.d_model)
    for k, v in base.items():
        out[f"{key_prefix}_{k}"] = v
    return out


def _dense_layer_init(cfg, key) -> Params:
    ks = split_keys(key, 2)
    p: Params = {}
    p.update({f"ln1_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p.update({f"ln2_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p["attn"] = attn.attn_init(cfg, ks[0])
    if cfg.moe is not None:
        p["moe"] = moe_mod.moe_init(cfg, ks[1])
    else:
        p["mlp"] = mlp_init(cfg, ks[1], cfg.d_model, cfg.d_ff)
    return p


def _rwkv_layer_init(cfg, key) -> Params:
    p: Params = {}
    p.update({f"ln1_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p.update({f"ln2_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p["rwkv"] = rwkv_mod.rwkv_init(cfg, key)
    return p


def _mamba_layer_init(cfg, key) -> Params:
    p: Params = {}
    p.update({f"ln1_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p["mamba"] = mamba_init_wrap(cfg, key)
    return p


def mamba_init_wrap(cfg, key):
    return mam.mamba_init(cfg, key)


def _whisper_enc_layer_init(cfg, key) -> Params:
    ks = split_keys(key, 2)
    p: Params = {}
    p.update({f"ln1_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p.update({f"ln2_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p["attn"] = attn.attn_init(cfg, ks[0])
    p["mlp"] = mlp_init(cfg, ks[1], cfg.d_model, cfg.d_ff)
    return p


def _whisper_dec_layer_init(cfg, key) -> Params:
    ks = split_keys(key, 3)
    p: Params = {}
    for nm in ("ln1", "ln2", "ln3"):
        p.update({f"{nm}_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p["attn"] = attn.attn_init(cfg, ks[0])
    p["cross"] = attn.cross_attn_init(cfg, ks[1])
    p["mlp"] = mlp_init(cfg, ks[2], cfg.d_model, cfg.d_ff)
    return p


def _mla_layer_init(cfg, key, dense: bool) -> Params:
    ks = split_keys(key, 2)
    p: Params = {}
    p.update({f"ln1_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p.update({f"ln2_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
    p["attn"] = attn.mla_init(cfg, ks[0])
    if dense:
        p["mlp"] = mlp_init(cfg, ks[1], cfg.d_model, cfg.d_ff)
    else:
        p["moe"] = moe_mod.moe_init(cfg, ks[1])
    return p


def _mla_dense_layers(cfg) -> int:
    """Leading layers of an MLA stack with a dense MLP; the rest are MoE.

    The MLA branch serves DeepSeek-V2 stacks only: latent attention with
    DeepSeekMoE experts on ``moe_forward_dropless`` (``cfg.moe.mode`` is
    not read). Latent attention over a dense MLP, or latent-attention MoE
    on another expert path, would need a branch of its own."""
    if cfg.moe is None:
        raise NotImplementedError("an MLA stack is DeepSeek-V2's: MLA with DeepSeekMoE experts")
    return cfg.moe.first_k_dense


def init_params(cfg, key) -> Params:
    """Random-init parameters; structure is family-dependent but stable."""
    dt = dtype_of(cfg.param_dtype)
    ks = split_keys(key, 8)
    p: Params = {"embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dt)}
    p.update(_norm_params(cfg, "final"))
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[1], (cfg.d_model, cfg.vocab_size), dt)

    if cfg.mla is not None:  # leading dense layers, then MoE layers
        k = _mla_dense_layers(cfg)
        if k:
            p["dense_blocks"] = _stack(cfg, lambda c, kk: _mla_layer_init(c, kk, True),
                                       ks[3], k)
        p["blocks"] = _stack(cfg, lambda c, kk: _mla_layer_init(c, kk, False),
                             ks[2], cfg.num_layers - k)
        return p

    if cfg.family == "audio":
        enc = cfg.encoder
        p["enc_blocks"] = _stack(cfg, _whisper_enc_layer_init, ks[2], enc.num_layers)
        p["dec_blocks"] = _stack(cfg, _whisper_dec_layer_init, ks[3], cfg.num_layers)
        p.update({f"enc_final_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
        p["dec_pos"] = dense_init(ks[4], (32_776, cfg.d_model), dt, scale=0.01)
        return p

    if cfg.family == "ssm":  # rwkv6
        p["blocks"] = _stack(cfg, _rwkv_layer_init, ks[2], cfg.num_layers)
        p.update({f"ln0_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()})
        return p

    if cfg.family == "hybrid":  # zamba2
        groups = cfg.num_layers // cfg.attn_every
        per = cfg.attn_every

        def group_init(k):
            return _stack(cfg, _mamba_layer_init, k, per)

        p["blocks"] = _stack(cfg, lambda c, k: group_init(k), ks[2], groups)
        p["shared_attn"] = _dense_layer_init(cfg, ks[3])
        return p

    # dense / moe / vlm
    p["blocks"] = _stack(cfg, _dense_layer_init, ks[2], cfg.num_layers)
    return p


def _stack(cfg, layer_init, key, n: int) -> Params:
    keys = jax.random.split(key, n)

    def one(k):
        try:
            return layer_init(cfg, k)
        except TypeError:
            return layer_init(k)

    return jax.vmap(one)(keys)


def abstract_params(cfg) -> Params:
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ===========================================================================
# Cache construction
# ===========================================================================


def init_cache(cfg, batch: int, max_len: int, *, enc_len: int = 0) -> Cache:
    """Zeroed cache pytree for ``batch`` sequences of up to ``max_len``."""
    kv_dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else dtype_of(cfg.dtype)
    c: Cache = {"length": jnp.zeros((), jnp.int32)}
    if cfg.mla is not None:
        c["kv_latent"] = jnp.zeros((cfg.num_layers, batch, max_len, cfg.mla.cache_dim), kv_dt)
        c[EXPERT_TOKENS] = jnp.zeros(expert_tokens_shape(cfg), jnp.int32)
        return c
    if cfg.family == "audio":
        L = cfg.num_layers
        c["kv_k"] = jnp.zeros((L, batch, max_len, cfg.num_kv_heads, cfg.head_dim), kv_dt)
        c["kv_v"] = jnp.zeros_like(c["kv_k"])
        T = enc_len or cfg.encoder.num_frames
        c["cross_k"] = jnp.zeros((L, batch, T, cfg.num_kv_heads, cfg.head_dim), kv_dt)
        c["cross_v"] = jnp.zeros_like(c["cross_k"])
        return c
    if cfg.family == "ssm":
        s = cfg.ssm
        H = cfg.d_model // s.head_dim
        L = cfg.num_layers
        c["ssm_state"] = jnp.zeros((L, batch, H, s.head_dim, s.head_dim), jnp.float32)
        c["shift_tm"] = jnp.zeros((L, batch, cfg.d_model), dtype_of(cfg.dtype))
        c["shift_cm"] = jnp.zeros_like(c["shift_tm"])
        return c
    if cfg.family == "hybrid":
        s = cfg.ssm
        d_in, heads, conv_ch = mam.mamba_dims(cfg)
        G = cfg.num_layers // cfg.attn_every
        per = cfg.attn_every
        c["ssm_state"] = jnp.zeros(
            (G, per, batch, heads, s.head_dim, s.state_dim), jnp.float32
        )
        c["conv"] = jnp.zeros(
            (G, per, batch, s.conv_dim - 1, conv_ch), dtype_of(cfg.dtype)
        )
        c["kv_k"] = jnp.zeros((G, batch, max_len, cfg.num_kv_heads, cfg.head_dim), kv_dt)
        c["kv_v"] = jnp.zeros_like(c["kv_k"])
        return c
    L = cfg.num_layers
    c["kv_k"] = jnp.zeros((L, batch, max_len, cfg.num_kv_heads, cfg.head_dim), kv_dt)
    c["kv_v"] = jnp.zeros_like(c["kv_k"])
    return c


def expert_tokens_shape(cfg) -> Optional[Tuple[int, int]]:
    """(MoE layers, experts) of the cache's ``EXPERT_TOKENS``, or None for
    a model whose cache carries none. Only MLA (DeepSeek-V2) stacks count
    their routed pairs: the other MoE paths (``moe_forward``) return none."""
    if cfg.mla is None:
        return None
    return cfg.num_layers - _mla_dense_layers(cfg), cfg.moe.num_experts


def abstract_cache(cfg, batch: int, max_len: int, **kw) -> Cache:
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, **kw))


# ===========================================================================
# Forward (train / prefill)
# ===========================================================================


def _embed(cfg, params: Params, batch: Batch, ctx) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (x (B,S,D), positions) handling the modality stubs."""
    if "embeds" in batch:  # vlm stub frontend: precomputed patch/token embeds
        x = batch["embeds"].astype(dtype_of(cfg.dtype))
        pos = batch.get("positions")
        if pos is None:
            B, S, _ = x.shape
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    else:
        tokens = batch["tokens"]
        x = jnp.take(params["embed"], tokens, axis=0).astype(dtype_of(cfg.dtype))
        B, S = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return x, pos


def _logits(cfg, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    x = apply_norm(cfg, params, "final", x)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return x @ w


def _dense_layer_fwd(cfg, p, x, cos, sin, ctx, want_cache):
    h, kv = attn.attention_seq(cfg, p["attn"], apply_norm(cfg, p, "ln1", x), cos, sin)
    x = x + h
    if cfg.moe is not None:
        m, aux = moe_mod.moe_forward(cfg, p["moe"], apply_norm(cfg, p, "ln2", x), ctx)
    else:
        m = mlp_forward(cfg, p["mlp"], apply_norm(cfg, p, "ln2", x))
        aux = jnp.zeros((), jnp.float32)
    x = x + m
    return x, aux, kv


def forward(
    cfg,
    params: Params,
    batch: Batch,
    ctx: Optional[ParallelCtx] = None,
    *,
    want_cache: bool = False,
    cache_len: Optional[int] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[Cache]]:
    """Full-sequence forward.

    Returns (logits (B,S,V), aux_loss, cache-or-None). When ``want_cache``,
    the cache covers ``cache_len`` positions (default S) with S filled.
    """
    if cfg.family == "audio":
        return _whisper_forward(cfg, params, batch, ctx, want_cache, cache_len)
    x, pos = _embed(cfg, params, batch, ctx)
    B, S, _ = x.shape
    bspec = None if ctx is None else P(ctx.batch_spec, ctx.seq_spec, None)
    x = _constrain(x, ctx, bspec)
    if cfg.mla is not None:
        return _mla_forward(cfg, params, x, pos, ctx, want_cache, cache_len)
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)

    if cfg.family == "ssm":
        x = apply_norm(cfg, params, "ln0", x)
        state0 = jnp.zeros(
            (B, cfg.d_model // cfg.ssm.head_dim, cfg.ssm.head_dim, cfg.ssm.head_dim),
            jnp.float32,
        )

        def body(carry, p):
            xc = carry
            y, st, sh_tm = rwkv_mod.rwkv_time_mix(
                cfg, p["rwkv"], apply_norm(cfg, p, "ln1", xc), state0, None
            )
            xc = xc + y
            y2, sh_cm = rwkv_mod.rwkv_channel_mix(
                cfg, p["rwkv"], apply_norm(cfg, p, "ln2", xc), None
            )
            xc = xc + y2
            xc = _constrain(xc, ctx, bspec)
            out = (st, sh_tm, sh_cm) if want_cache else None
            return xc, out

        x, outs = jax.lax.scan(_maybe_remat(body, ctx), x, params["blocks"])
        logits = _logits(cfg, params, x)
        cache = None
        if want_cache:
            st, sh_tm, sh_cm = outs
            cache = {
                "length": jnp.asarray(S, jnp.int32),
                "ssm_state": st,
                "shift_tm": sh_tm,
                "shift_cm": sh_cm,
            }
        return logits, jnp.zeros((), jnp.float32), cache

    if cfg.family == "hybrid":
        return _hybrid_forward(cfg, params, x, cos, sin, ctx, want_cache, cache_len, S)

    # dense / moe / vlm
    def body(carry, p):
        xc, aux = carry
        xo, a, kv = _dense_layer_fwd(cfg, p, xc, cos, sin, ctx, want_cache)
        xo = _constrain(xo, ctx, bspec)
        return (xo, aux + a), (kv if want_cache else None)

    (x, aux), kvs = jax.lax.scan(
        _maybe_remat(body, ctx), (x, jnp.zeros((), jnp.float32)), params["blocks"]
    )
    logits = _logits(cfg, params, x)
    cache = None
    if want_cache:
        k_all, v_all = kvs  # (L, B, S, Hkv, hd)
        M = cache_len or S
        kv_dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else x.dtype
        if M > S:
            padk = jnp.zeros(
                (cfg.num_layers, B, M - S, cfg.num_kv_heads, cfg.head_dim), kv_dt
            )
            k_all = jnp.concatenate([k_all.astype(kv_dt), padk], axis=2)
            v_all = jnp.concatenate([v_all.astype(kv_dt), padk], axis=2)
        cache = {
            "length": jnp.asarray(S, jnp.int32),
            "kv_k": k_all.astype(kv_dt),
            "kv_v": v_all.astype(kv_dt),
        }
    return logits, aux, cache


def _mla_angles(cfg, pos):
    return yarn_angles(pos, cfg.mla.qk_rope_head_dim, cfg.rope_theta, cfg.yarn)


def _split_experts(blocks):
    """The MoE stack without its routed experts' weights (scanned per
    layer), and those weights whole: a decode step slices its routed
    experts straight out of the stack, where a per-layer slice of the
    scan would have XLA copy all of the layer's experts first."""
    experts = {n: blocks["moe"][n] for n in moe_mod.EXPERT_WEIGHTS if n in blocks["moe"]}
    rest = {k: v for k, v in blocks["moe"].items() if k not in experts}
    return {**blocks, "moe": rest}, experts


def _mla_forward(cfg, params, x, pos, ctx, want_cache, cache_len):
    B, S, _ = x.shape
    bspec = None if ctx is None else P(ctx.batch_spec, ctx.seq_spec, None)
    cos, sin = _mla_angles(cfg, pos)

    def attend(p, xc):
        h, rows = attn.mla_attention_seq(cfg, p["attn"], apply_norm(cfg, p, "ln1", xc), cos, sin)
        return xc + h, rows

    def dense_body(xc, p):
        xc, rows = attend(p, xc)
        xc = xc + mlp_forward(cfg, p["mlp"], apply_norm(cfg, p, "ln2", xc))
        return _constrain(xc, ctx, bspec), rows

    blocks, experts = _split_experts(params["blocks"])

    def moe_body(carry, inp):
        (xc, aux), (p, i) = carry, inp
        xc, rows = attend(p, xc)
        m, a, counts = moe_mod.moe_forward_dropless(
            cfg, {**p["moe"], **experts}, apply_norm(cfg, p, "ln2", xc), layer=i)
        return (_constrain(xc + m, ctx, bspec), aux + a), (rows, counts)

    rows = []
    if "dense_blocks" in params:
        x, r0 = jax.lax.scan(_maybe_remat(dense_body, ctx), x, params["dense_blocks"])
        rows.append(r0)
    (x, aux), (r1, counts) = jax.lax.scan(
        _maybe_remat(moe_body, ctx), (x, jnp.zeros((), jnp.float32)),
        (blocks, jnp.arange(cfg.num_layers - _mla_dense_layers(cfg))))
    rows.append(r1)
    logits = _logits(cfg, params, x)
    cache = None
    if want_cache:
        kv_dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else x.dtype
        lat = jnp.concatenate(rows, axis=0).astype(kv_dt)  # (L, B, S, C)
        M = cache_len or S
        if M > S:
            lat = jnp.pad(lat, ((0, 0), (0, 0), (0, M - S), (0, 0)))
        cache = {"length": jnp.asarray(S, jnp.int32), "kv_latent": lat,
                 EXPERT_TOKENS: counts}
    return logits, aux, cache


def _hybrid_forward(cfg, params, x, cos, sin, ctx, want_cache, cache_len, S):
    B = x.shape[0]
    s = cfg.ssm
    d_in, heads, conv_ch = mam.mamba_dims(cfg)
    bspec = None if ctx is None else P(ctx.batch_spec, ctx.seq_spec, None)
    shared = params["shared_attn"]
    state0 = jnp.zeros((B, heads, s.head_dim, s.state_dim), jnp.float32)

    def body(carry, p_group):
        xc, aux = carry
        states = []
        convs = []
        for i in range(cfg.attn_every):
            p_l = jax.tree.map(lambda a: a[i], p_group)
            y, st, cv = mam.mamba_forward(
                cfg, p_l["mamba"], apply_norm(cfg, p_l, "ln1", xc), state0, None
            )
            xc = xc + y
            states.append(st)
            convs.append(cv)
        xo, a, kv = _dense_layer_fwd(cfg, shared, xc, cos, sin, ctx, True)
        xo = _constrain(xo, ctx, bspec)
        out = None
        if want_cache:
            out = (jnp.stack(states), jnp.stack(convs), kv)
        return (xo, aux + a), out

    (x, aux), outs = jax.lax.scan(
        _maybe_remat(body, ctx), (x, jnp.zeros((), jnp.float32)), params["blocks"]
    )
    logits = _logits(cfg, params, x)
    cache = None
    if want_cache:
        st, cv, (k_all, v_all) = outs
        M = cache_len or S
        kv_dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else x.dtype
        if M > S:
            G = cfg.num_layers // cfg.attn_every
            padk = jnp.zeros((G, B, M - S, cfg.num_kv_heads, cfg.head_dim), kv_dt)
            k_all = jnp.concatenate([k_all.astype(kv_dt), padk], axis=2)
            v_all = jnp.concatenate([v_all.astype(kv_dt), padk], axis=2)
        cache = {
            "length": jnp.asarray(S, jnp.int32),
            "ssm_state": st,
            "conv": cv[:, :, :, -(s.conv_dim - 1) :, :],
            "kv_k": k_all.astype(kv_dt),
            "kv_v": v_all.astype(kv_dt),
        }
    return logits, aux, cache


def _whisper_forward(cfg, params, batch, ctx, want_cache, cache_len):
    frames = batch["frames"].astype(dtype_of(cfg.dtype))  # (B, T, D) stub frontend
    tokens = batch["tokens"]
    B, T, _ = frames.shape
    S = tokens.shape[1]
    # sinusoidal encoder positions
    pos = jnp.arange(T)
    half = cfg.d_model // 2
    freq = jnp.exp(-jnp.arange(half) * (jnp.log(10_000.0) / (half - 1)))
    sinus = jnp.concatenate(
        [jnp.sin(pos[:, None] * freq), jnp.cos(pos[:, None] * freq)], -1
    )
    xe = frames + sinus[None].astype(frames.dtype)

    def enc_body(carry, p):
        xc = carry
        h, _ = attn.attention_seq(
            cfg, p["attn"], apply_norm(cfg, p, "ln1", xc), None, None, causal=False
        )
        xc = xc + h
        xc = xc + mlp_forward(cfg, p["mlp"], apply_norm(cfg, p, "ln2", xc))
        return xc, None

    xe, _ = jax.lax.scan(enc_body, xe, params["enc_blocks"])
    enc_out = apply_norm(cfg, params, "enc_final", xe)

    xd = jnp.take(params["embed"], tokens, axis=0).astype(dtype_of(cfg.dtype))
    xd = xd + params["dec_pos"][:S][None].astype(xd.dtype)

    def dec_body(carry, p):
        xc = carry
        h, kv = attn.attention_seq(
            cfg, p["attn"], apply_norm(cfg, p, "ln1", xc), None, None, causal=True
        )
        xc = xc + h
        ck, cv = attn.cross_attention_kv(cfg, p["cross"], enc_out)
        xc = xc + attn.cross_attention(
            cfg, p["cross"], apply_norm(cfg, p, "ln2", xc), ck, cv
        )
        xc = xc + mlp_forward(cfg, p["mlp"], apply_norm(cfg, p, "ln3", xc))
        return xc, (kv, (ck, cv)) if want_cache else None

    xd, outs = jax.lax.scan(_maybe_remat(dec_body, ctx), xd, params["dec_blocks"])
    logits = _logits(cfg, params, xd)
    cache = None
    if want_cache:
        (k_all, v_all), (ck_all, cv_all) = outs
        M = cache_len or S
        kv_dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else xd.dtype
        if M > S:
            padk = jnp.zeros(
                (cfg.num_layers, B, M - S, cfg.num_kv_heads, cfg.head_dim), kv_dt
            )
            k_all = jnp.concatenate([k_all.astype(kv_dt), padk], axis=2)
            v_all = jnp.concatenate([v_all.astype(kv_dt), padk], axis=2)
        cache = {
            "length": jnp.asarray(S, jnp.int32),
            "kv_k": k_all.astype(kv_dt),
            "kv_v": v_all.astype(kv_dt),
            "cross_k": ck_all.astype(kv_dt),
            "cross_v": cv_all.astype(kv_dt),
        }
    return logits, jnp.zeros((), jnp.float32), cache


def prefill(cfg, params, batch, ctx=None, cache_len=None):
    logits, aux, cache = forward(
        cfg, params, batch, ctx, want_cache=True, cache_len=cache_len
    )
    return logits, cache


def prefill_extend(
    cfg,
    params: Params,
    batch: Batch,
    prefix_k: jnp.ndarray,
    prefix_v: jnp.ndarray,
    prefix_len: int,
    ctx: Optional[ParallelCtx] = None,
    cache_len: Optional[int] = None,
) -> Tuple[jnp.ndarray, Cache]:
    """Prefill ONLY a suffix against an already-built prefix KV.

    The suffix tokens (``batch["tokens"]``, (B, S)) are run at positions
    ``[prefix_len, prefix_len + S)`` attending over the prefix K/V plus
    themselves causally; the returned cache is the same dense pytree a
    full ``prefill`` of prefix+suffix would produce (prefix K/V copied
    into place), so ``decode_step`` continues transparently.

    prefix_k/v: (L, B, Sp, Hkv, hd) post-RoPE (Sp >= prefix_len; the
    overhang is page padding). prefix_len must be static under jit.
    KV-recurrent families keep per-token state, so a stored prefix can't
    be re-entered mid-stream — dense / moe / vlm only.
    """
    if cfg.family in ("audio", "ssm", "hybrid"):
        raise NotImplementedError(
            f"prefix extension requires a pure-KV cache; family "
            f"{cfg.family!r} carries recurrent state"
        )
    if cfg.mla is not None:
        raise NotImplementedError(
            f"prefix extension requires per-head K/V; {cfg.name!r} caches a "
            f"latent per position"
        )
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype_of(cfg.dtype))
    pos = jnp.broadcast_to(
        prefix_len + jnp.arange(S, dtype=jnp.int32)[None], (B, S)
    )
    bspec = None if ctx is None else P(ctx.batch_spec, ctx.seq_spec, None)
    x = _constrain(x, ctx, bspec)
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)

    def body(carry, inp):
        xc, aux = carry
        p, pk, pv = inp
        h, kv = attn.attention_extend(
            cfg, p["attn"], apply_norm(cfg, p, "ln1", xc), cos, sin,
            pk, pv, prefix_len,
        )
        xc = xc + h
        if cfg.moe is not None:
            m, a = moe_mod.moe_forward(cfg, p["moe"], apply_norm(cfg, p, "ln2", xc), ctx)
        else:
            m = mlp_forward(cfg, p["mlp"], apply_norm(cfg, p, "ln2", xc))
            a = jnp.zeros((), jnp.float32)
        xc = xc + m
        xc = _constrain(xc, ctx, bspec)
        return (xc, aux + a), kv

    (x, _), kvs = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (params["blocks"], prefix_k, prefix_v),
    )
    logits = _logits(cfg, params, x)
    k_suf, v_suf = kvs  # (L, B, S, Hkv, hd)
    total = prefix_len + S
    M = cache_len or total
    assert M >= total, (M, total)
    kv_dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else x.dtype

    def assemble(pre, suf):
        parts = [pre[:, :, :prefix_len].astype(kv_dt), suf.astype(kv_dt)]
        if M > total:
            parts.append(jnp.zeros(
                (cfg.num_layers, B, M - total, cfg.num_kv_heads, cfg.head_dim),
                kv_dt,
            ))
        return jnp.concatenate(parts, axis=2)

    cache = {
        "length": jnp.asarray(total, jnp.int32),
        "kv_k": assemble(prefix_k, k_suf),
        "kv_v": assemble(prefix_v, v_suf),
    }
    return logits, cache


# ===========================================================================
# Decode step
# ===========================================================================


def decode_step(
    cfg,
    params: Params,
    cache: Cache,
    tokens: jnp.ndarray,
    ctx: Optional[ParallelCtx] = None,
) -> Tuple[jnp.ndarray, Cache]:
    """One decode step. tokens: (B, 1) int32 (or embeds for vlm handled
    upstream). Returns (logits (B, 1, V), new cache)."""
    length = cache["length"]
    B = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype_of(cfg.dtype))
    pos = jnp.broadcast_to(length[None, None], (B, 1)).astype(jnp.int32)
    if cfg.mla is not None:
        return _mla_decode(cfg, params, cache, x, pos)
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)

    if cfg.family == "ssm":
        x2 = apply_norm(cfg, params, "ln0", x)[:, 0]  # (B, D)

        def body(carry, inp):
            xc = carry
            p, st, sh_tm, sh_cm = inp
            xn = apply_norm(cfg, p, "ln1", xc)
            y, st, sh_tm = rwkv_mod.rwkv_time_mix_step(cfg, p["rwkv"], xn, st, sh_tm)
            xc = xc + y
            xn = apply_norm(cfg, p, "ln2", xc)
            y2, sh_cm = rwkv_mod.rwkv_channel_mix(cfg, p["rwkv"], xn, sh_cm)
            xc = xc + y2
            return xc, (st, sh_tm, sh_cm)

        x2, (st, sh_tm, sh_cm) = jax.lax.scan(
            body, x2, (params["blocks"], cache["ssm_state"], cache["shift_tm"], cache["shift_cm"])
        )
        logits = _logits(cfg, params, x2[:, None])
        new_cache = {
            "length": length + 1,
            "ssm_state": st,
            "shift_tm": sh_tm,
            "shift_cm": sh_cm,
        }
        return logits, new_cache

    if cfg.family == "hybrid":
        return _hybrid_decode(cfg, params, cache, x, cos, sin, ctx)

    if cfg.family == "audio":
        return _whisper_decode(cfg, params, cache, x, ctx)

    def body(carry, inp):
        xc = carry
        p, ck, cv = inp
        h, ck, cv = attn.attention_decode(
            cfg, p["attn"], apply_norm(cfg, p, "ln1", xc), cos, sin, ck, cv, length
        )
        xc = xc + h
        if cfg.moe is not None:
            m, _ = moe_mod.moe_forward(cfg, p["moe"], apply_norm(cfg, p, "ln2", xc), ctx)
        else:
            m = mlp_forward(cfg, p["mlp"], apply_norm(cfg, p, "ln2", xc))
        xc = xc + m
        return xc, (ck, cv)

    x, (ck, cv) = jax.lax.scan(body, x, (params["blocks"], cache["kv_k"], cache["kv_v"]))
    logits = _logits(cfg, params, x)
    return logits, {"length": length + 1, "kv_k": ck, "kv_v": cv}


def _mla_decode(cfg, params, cache, x, pos):
    """The whole latent cache rides the layer loops' carry: each layer
    writes its new row in place and reads its own rows."""
    length = cache["length"]
    cos, sin = _mla_angles(cfg, pos)
    k = _mla_dense_layers(cfg)

    def attend(p, xc, lat, layer):
        h, lat = attn.mla_attention_decode(cfg, p["attn"], apply_norm(cfg, p, "ln1", xc),
                                           cos, sin, lat, layer, length)
        return xc + h, lat

    def dense_body(carry, inp):
        (xc, lat), (p, layer) = carry, inp
        xc, lat = attend(p, xc, lat, layer)
        return (xc + mlp_forward(cfg, p["mlp"], apply_norm(cfg, p, "ln2", xc)), lat), None

    blocks, experts = _split_experts(params["blocks"])

    def moe_body(carry, inp):
        (xc, lat), (p, layer) = carry, inp
        xc, lat = attend(p, xc, lat, layer)
        m, _, counts = moe_mod.moe_forward_dropless(
            cfg, {**p["moe"], **experts}, apply_norm(cfg, p, "ln2", xc), layer=layer - k)
        return (xc + m, lat), counts

    carry = (x, cache["kv_latent"])
    if k:
        carry, _ = jax.lax.scan(dense_body, carry,
                                (params["dense_blocks"], jnp.arange(k)))
    (x, lat), counts = jax.lax.scan(moe_body, carry,
                                    (blocks, jnp.arange(k, cfg.num_layers)))
    logits = _logits(cfg, params, x)
    return logits, {"length": length + 1, "kv_latent": lat,
                    EXPERT_TOKENS: cache[EXPERT_TOKENS] + counts}


def _hybrid_decode(cfg, params, cache, x, cos, sin, ctx):
    length = cache["length"]
    shared = params["shared_attn"]
    x2 = x[:, 0]

    def body(carry, inp):
        xc = carry
        p_group, st_g, cv_g, ck, cvv = inp
        sts = []
        cvs = []
        for i in range(cfg.attn_every):
            p_l = jax.tree.map(lambda a: a[i], p_group)
            xn = apply_norm(cfg, p_l, "ln1", xc)
            y, st, cvx = mam.mamba_step(cfg, p_l["mamba"], xn, st_g[i], cv_g[i])
            xc = xc + y
            sts.append(st)
            cvs.append(cvx)
        # shared attention block (on (B,1,D))
        x3 = xc[:, None]
        h, ck, cvv = attn.attention_decode(
            cfg, shared["attn"], apply_norm(cfg, shared, "ln1", x3), cos, sin, ck, cvv, length
        )
        x3 = x3 + h
        x3 = x3 + mlp_forward(cfg, shared["mlp"], apply_norm(cfg, shared, "ln2", x3))
        return x3[:, 0], (jnp.stack(sts), jnp.stack(cvs), ck, cvv)

    x2, (st, cv, ck, cvv) = jax.lax.scan(
        body,
        x2,
        (params["blocks"], cache["ssm_state"], cache["conv"], cache["kv_k"], cache["kv_v"]),
    )
    logits = _logits(cfg, params, x2[:, None])
    return logits, {
        "length": length + 1,
        "ssm_state": st,
        "conv": cv,
        "kv_k": ck,
        "kv_v": cvv,
    }


def _whisper_decode(cfg, params, cache, x, ctx):
    length = cache["length"]
    pos_emb = jax.lax.dynamic_index_in_dim(params["dec_pos"], length, keepdims=True)
    x = x + pos_emb[None].astype(x.dtype)

    def body(carry, inp):
        xc = carry
        p, ck, cv, crk, crv = inp
        h, ck, cv = attn.attention_decode(
            cfg, p["attn"], apply_norm(cfg, p, "ln1", xc), None, None, ck, cv, length
        )
        xc = xc + h
        xc = xc + attn.cross_attention(
            cfg, p["cross"], apply_norm(cfg, p, "ln2", xc), crk, crv
        )
        xc = xc + mlp_forward(cfg, p["mlp"], apply_norm(cfg, p, "ln3", xc))
        return xc, (ck, cv)

    x, (ck, cv) = jax.lax.scan(
        body,
        x,
        (
            params["dec_blocks"],
            cache["kv_k"],
            cache["kv_v"],
            cache["cross_k"],
            cache["cross_v"],
        ),
    )
    logits = _logits(cfg, params, x)
    return logits, {
        "length": length + 1,
        "kv_k": ck,
        "kv_v": cv,
        "cross_k": cache["cross_k"],
        "cross_v": cache["cross_v"],
    }
