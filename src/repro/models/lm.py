"""Unified language model: one init/forward/prefill/decode API for every
assigned architecture family (dense, moe, ssm, hybrid, vlm, audio enc-dec).

Layer stacks run under ``lax.scan`` with stacked per-layer parameters
(leading L axis) — production pattern: O(1) HLO size in depth, FSDP
all-gathers live inside the loop body (roofline.py multiplies while-body
costs by trip count, so accounting stays exact).

Each family's parameters, cache, forward and decode step are functions of
one ``_Family`` entry, and ``_family`` alone chooses the entry for a
config. Caches are explicit pytrees whose arrays, shapes and dtypes
``init_cache`` alone decides: prefill and prefix extension write their rows
into that layout (``_filled_cache``), so serving code jits ``decode_step``
with donated cache buffers. Decode carries each stacked cache through its
layer scan and writes a layer's new row in place, so the donated buffer is
the returned one and no step copies the cache. A latent-attention (MLA)
model (DeepSeek-V2: MLA with DeepSeekMoE experts, the only MLA stack
served) caches one ``(L, B, M, kv_lora_rank + qk_rope_head_dim)`` array
(``kv_latent``) in place of ``kv_k``/``kv_v``, and the routed (token,
expert) pairs per MoE layer and expert so far (``expert_tokens``,
``(L_moe, E)`` int32), summed on the device by prefill and each decode
step.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

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


def _bspec(ctx: Optional[ParallelCtx]):
    """The (batch, sequence, model) spec of activations under ``ctx``."""
    return None if ctx is None else P(ctx.batch_spec, ctx.seq_spec, None)


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
    return {f"{key_prefix}_{k}": v for k, v in norm_param_init(cfg, cfg.d_model).items()}


def _dense_layer_init(cfg, key) -> Params:
    ks = split_keys(key, 2)
    p: Params = {**_norm_params(cfg, "ln1"), **_norm_params(cfg, "ln2")}
    p["attn"] = attn.attn_init(cfg, ks[0])
    if cfg.moe is not None:
        p["moe"] = moe_mod.moe_init(cfg, ks[1])
    else:
        p["mlp"] = mlp_init(cfg, ks[1], cfg.d_model, cfg.d_ff)
    return p


def _rwkv_layer_init(cfg, key) -> Params:
    p: Params = {**_norm_params(cfg, "ln1"), **_norm_params(cfg, "ln2")}
    p["rwkv"] = rwkv_mod.rwkv_init(cfg, key)
    return p


def _mamba_layer_init(cfg, key) -> Params:
    return {**_norm_params(cfg, "ln1"), "mamba": mam.mamba_init(cfg, key)}


def _whisper_dec_layer_init(cfg, key) -> Params:
    ks = split_keys(key, 3)
    p: Params = {**_norm_params(cfg, "ln1"), **_norm_params(cfg, "ln2"),
                 **_norm_params(cfg, "ln3")}
    p["attn"] = attn.attn_init(cfg, ks[0])
    p["cross"] = attn.cross_attn_init(cfg, ks[1])
    p["mlp"] = mlp_init(cfg, ks[2], cfg.d_model, cfg.d_ff)
    return p


def _mla_layer_init(cfg, key, dense: bool) -> Params:
    ks = split_keys(key, 2)
    p: Params = {**_norm_params(cfg, "ln1"), **_norm_params(cfg, "ln2")}
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
    p.update(_family(cfg).params(cfg, ks, dt))
    return p


def _stack(cfg, layer_init, key, n: int) -> Params:
    return jax.vmap(lambda k: layer_init(cfg, k))(jax.random.split(key, n))


def _dense_params(cfg, ks, dt) -> Params:
    return {"blocks": _stack(cfg, _dense_layer_init, ks[2], cfg.num_layers)}


def _mla_params(cfg, ks, dt) -> Params:
    """Leading dense layers, then MoE layers."""
    k = _mla_dense_layers(cfg)
    p = {"blocks": _stack(cfg, lambda c, kk: _mla_layer_init(c, kk, False),
                          ks[2], cfg.num_layers - k)}
    if k:
        p["dense_blocks"] = _stack(cfg, lambda c, kk: _mla_layer_init(c, kk, True), ks[3], k)
    return p


def _rwkv_params(cfg, ks, dt) -> Params:
    return {"blocks": _stack(cfg, _rwkv_layer_init, ks[2], cfg.num_layers),
            **_norm_params(cfg, "ln0")}


def _hybrid_params(cfg, ks, dt) -> Params:
    """Groups of ``attn_every`` Mamba layers, then one shared attention block."""
    def group_init(c, k):
        return _stack(c, _mamba_layer_init, k, c.attn_every)

    return {"blocks": _stack(cfg, group_init, ks[2], cfg.num_layers // cfg.attn_every),
            "shared_attn": _dense_layer_init(cfg, ks[3])}


def _whisper_params(cfg, ks, dt) -> Params:
    return {
        "enc_blocks": _stack(cfg, _dense_layer_init, ks[2], cfg.encoder.num_layers),
        "dec_blocks": _stack(cfg, _whisper_dec_layer_init, ks[3], cfg.num_layers),
        **_norm_params(cfg, "enc_final"),
        "dec_pos": dense_init(ks[4], (32_776, cfg.d_model), dt, scale=0.01),
    }


def abstract_params(cfg) -> Params:
    """ShapeDtypeStruct pytree — no allocation (dry-run path)."""
    return jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


# ===========================================================================
# Cache construction
# ===========================================================================


def init_cache(cfg, batch: int, max_len: int, *, enc_len: int = 0) -> Cache:
    """Zeroed cache pytree for ``batch`` sequences of up to ``max_len``.

    The one definition of a cache's arrays, shapes and dtypes: K/V and
    latent caches are kept in the activation dtype, recurrent states as
    each family's own function gives them."""
    c: Cache = {"length": jnp.zeros((), jnp.int32)}
    c.update(_family(cfg).cache(cfg, batch, max_len, enc_len, dtype_of(cfg.dtype)))
    return c


def _kv_cache(cfg, layers: int, batch: int, max_len: int, dt) -> Cache:
    k = jnp.zeros((layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim), dt)
    return {"kv_k": k, "kv_v": jnp.zeros_like(k)}


def _dense_cache(cfg, batch, max_len, enc_len, dt) -> Cache:
    return _kv_cache(cfg, cfg.num_layers, batch, max_len, dt)


def _mla_cache(cfg, batch, max_len, enc_len, dt) -> Cache:
    L = cfg.num_layers
    return {
        "kv_latent": jnp.zeros((L, batch, max_len, cfg.mla.cache_dim), dt),
        EXPERT_TOKENS: jnp.zeros((L - _mla_dense_layers(cfg), cfg.moe.num_experts), jnp.int32),
    }


def _rwkv_cache(cfg, batch, max_len, enc_len, dt) -> Cache:
    s, L = cfg.ssm, cfg.num_layers
    shift = jnp.zeros((L, batch, cfg.d_model), dt)
    return {
        "ssm_state": jnp.zeros(
            (L, batch, cfg.d_model // s.head_dim, s.head_dim, s.head_dim), jnp.float32),
        "shift_tm": shift,
        "shift_cm": jnp.zeros_like(shift),
    }


def _hybrid_cache(cfg, batch, max_len, enc_len, dt) -> Cache:
    s = cfg.ssm
    _, heads, conv_ch = mam.mamba_dims(cfg)
    G, per = cfg.num_layers // cfg.attn_every, cfg.attn_every
    return {
        "ssm_state": jnp.zeros((G, per, batch, heads, s.head_dim, s.state_dim), jnp.float32),
        "conv": jnp.zeros((G, per, batch, s.conv_dim - 1, conv_ch), dt),
        **_kv_cache(cfg, G, batch, max_len, dt),
    }


def _whisper_cache(cfg, batch, max_len, enc_len, dt) -> Cache:
    L, T = cfg.num_layers, enc_len or cfg.encoder.num_frames
    cross = jnp.zeros((L, batch, T, cfg.num_kv_heads, cfg.head_dim), dt)
    return {**_kv_cache(cfg, L, batch, max_len, dt),
            "cross_k": cross, "cross_v": jnp.zeros_like(cross)}


def _filled_cache(cfg, batch: int, cache_len: Optional[int], length: int,
                  rows: Cache, *, enc_len: int = 0) -> Cache:
    """``init_cache(cfg, batch, cache_len or length)`` with ``rows`` (every
    key but ``length``) written in, in the layout's dtypes: a K/V or latent
    array fills positions [0, length), a state the whole array, and the
    rest stays zero. Each cache byte is written once."""
    layout = abstract_cache(cfg, batch, cache_len or length, enc_len=enc_len)
    cache: Cache = {"length": jnp.asarray(length, jnp.int32)}
    for k, want in layout.items():
        if k != "length":
            v = rows[k]
            cache[k] = jnp.pad(v.astype(want.dtype),
                               [(0, n - m) for n, m in zip(want.shape, v.shape)])
    return cache


def expert_tokens_shape(cfg) -> Optional[Tuple[int, ...]]:
    """(MoE layers, experts) of the cache's ``EXPERT_TOKENS``, or None for
    a model whose cache carries none. Only MLA (DeepSeek-V2) stacks count
    their routed pairs: the other MoE paths (``moe_forward``) return none."""
    routed = abstract_cache(cfg, 1, 1).get(EXPERT_TOKENS)
    return None if routed is None else routed.shape


def abstract_cache(cfg, batch: int, max_len: int, **kw) -> Cache:
    return jax.eval_shape(lambda: init_cache(cfg, batch, max_len, **kw))


def prefix_refusal(cfg) -> Optional[str]:
    """Why a stored per-head K/V prefix (``serving/kv_cache.py``'s page
    pool) cannot be re-entered into this model's cache, or None where it
    can: only a cache of nothing but per-head K/V takes one."""
    return _family(cfg).no_prefix


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
    return _constrain(x, ctx, _bspec(ctx)), pos


def _logits(cfg, params: Params, x: jnp.ndarray) -> jnp.ndarray:
    x = apply_norm(cfg, params, "final", x)
    w = params["embed"].T if cfg.tie_embeddings else params["head"]
    return x @ w


def _ffn(cfg, p, x, ctx):
    """A layer's MoE or MLP block on ``x``, residual added: (x, aux loss)."""
    h = apply_norm(cfg, p, "ln2", x)
    if cfg.moe is not None:
        m, aux = moe_mod.moe_forward(cfg, p["moe"], h, ctx)
    else:
        m, aux = mlp_forward(cfg, p["mlp"], h), jnp.zeros((), jnp.float32)
    return x + m, aux


def _dense_layer_fwd(cfg, p, x, cos, sin, ctx):
    h, kv = attn.attention_seq(cfg, p["attn"], apply_norm(cfg, p, "ln1", x), cos, sin)
    x, aux = _ffn(cfg, p, x + h, ctx)
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
    return _family(cfg).forward(cfg, params, batch, ctx, want_cache, cache_len)


def _dense_forward(cfg, params, batch, ctx, want_cache, cache_len):
    """dense / moe / vlm"""
    x, pos = _embed(cfg, params, batch, ctx)
    B, S, _ = x.shape
    bspec = _bspec(ctx)
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)

    def body(carry, p):
        xc, aux = carry
        xo, a, kv = _dense_layer_fwd(cfg, p, xc, cos, sin, ctx)
        xo = _constrain(xo, ctx, bspec)
        return (xo, aux + a), (kv if want_cache else None)

    (x, aux), kvs = jax.lax.scan(
        _maybe_remat(body, ctx), (x, jnp.zeros((), jnp.float32)), params["blocks"]
    )
    logits = _logits(cfg, params, x)
    cache = None
    if want_cache:
        k_all, v_all = kvs  # (L, B, S, Hkv, hd)
        cache = _filled_cache(cfg, B, cache_len, S, {"kv_k": k_all, "kv_v": v_all})
    return logits, aux, cache


def _rwkv_forward(cfg, params, batch, ctx, want_cache, cache_len):
    x, _ = _embed(cfg, params, batch, ctx)
    B, S, _ = x.shape
    bspec = _bspec(ctx)
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
        cache = _filled_cache(cfg, B, cache_len, S,
                              {"ssm_state": st, "shift_tm": sh_tm, "shift_cm": sh_cm})
    return logits, jnp.zeros((), jnp.float32), cache


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


def _mla_forward(cfg, params, batch, ctx, want_cache, cache_len):
    x, pos = _embed(cfg, params, batch, ctx)
    B, S, _ = x.shape
    bspec = _bspec(ctx)
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
        cache = _filled_cache(cfg, B, cache_len, S, {
            "kv_latent": jnp.concatenate(rows, axis=0),  # (L, B, S, C)
            EXPERT_TOKENS: counts,
        })
    return logits, aux, cache


def _hybrid_forward(cfg, params, batch, ctx, want_cache, cache_len):
    x, pos = _embed(cfg, params, batch, ctx)
    B, S, _ = x.shape
    s = cfg.ssm
    _, heads, _ = mam.mamba_dims(cfg)
    bspec = _bspec(ctx)
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)
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
        xo, a, kv = _dense_layer_fwd(cfg, shared, xc, cos, sin, ctx)
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
        cache = _filled_cache(cfg, B, cache_len, S, {
            "ssm_state": st,
            "conv": cv[:, :, :, -(s.conv_dim - 1) :, :],
            "kv_k": k_all,
            "kv_v": v_all,
        })
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
        cache = _filled_cache(cfg, B, cache_len, S, {
            "kv_k": k_all, "kv_v": v_all, "cross_k": ck_all, "cross_v": cv_all,
        }, enc_len=T)
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
    Only a cache of nothing but per-head K/V takes a stored prefix
    (``prefix_refusal``); any other raises ``NotImplementedError``.
    """
    refusal = prefix_refusal(cfg)
    if refusal is not None:
        raise NotImplementedError(f"prefix extension of {cfg.name}: {refusal}")
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(dtype_of(cfg.dtype))
    pos = jnp.broadcast_to(
        prefix_len + jnp.arange(S, dtype=jnp.int32)[None], (B, S)
    )
    bspec = _bspec(ctx)
    x = _constrain(x, ctx, bspec)
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)

    def body(carry, inp):
        xc, aux = carry
        p, pk, pv = inp
        h, kv = attn.attention_extend(
            cfg, p["attn"], apply_norm(cfg, p, "ln1", xc), cos, sin,
            pk, pv, prefix_len,
        )
        xc, a = _ffn(cfg, p, xc + h, ctx)
        return (_constrain(xc, ctx, bspec), aux + a), kv

    (x, _), kvs = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (params["blocks"], prefix_k, prefix_v),
    )
    logits = _logits(cfg, params, x)
    k_suf, v_suf = kvs  # (L, B, S, Hkv, hd)

    def rows(pre, suf):
        return jnp.concatenate([pre[:, :, :prefix_len].astype(suf.dtype), suf], axis=2)

    cache = _filled_cache(cfg, B, cache_len, prefix_len + S,
                          {"kv_k": rows(prefix_k, k_suf), "kv_v": rows(prefix_v, v_suf)})
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
    return _family(cfg).decode(cfg, params, cache, x, pos, ctx)


def _dense_decode(cfg, params, cache, x, pos, ctx):
    length = cache["length"]
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)

    def body(carry, inp):
        xc, ck, cv = carry
        p, layer = inp
        h, ck, cv = attn.attention_decode(
            cfg, p["attn"], apply_norm(cfg, p, "ln1", xc), cos, sin, ck, cv, layer, length
        )
        xc, _ = _ffn(cfg, p, xc + h, ctx)
        return (xc, ck, cv), None

    (x, ck, cv), _ = jax.lax.scan(
        body, (x, cache["kv_k"], cache["kv_v"]),
        (params["blocks"], jnp.arange(cfg.num_layers)),
    )
    logits = _logits(cfg, params, x)
    return logits, {"length": length + 1, "kv_k": ck, "kv_v": cv}


def _rwkv_decode(cfg, params, cache, x, pos, ctx):
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
    return logits, {
        "length": cache["length"] + 1,
        "ssm_state": st,
        "shift_tm": sh_tm,
        "shift_cm": sh_cm,
    }


def _mla_decode(cfg, params, cache, x, pos, ctx):
    """Decode through the dense then MoE layer scans; the latent cache
    rides their carry as the dense K/V cache rides ``_dense_decode``'s."""
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


def _hybrid_decode(cfg, params, cache, x, pos, ctx):
    length = cache["length"]
    cos, sin = positions_for_rope(cfg, pos, cfg.head_dim)
    shared = params["shared_attn"]
    x2 = x[:, 0]

    def body(carry, inp):
        xc, ck, cvv = carry
        p_group, st_g, cv_g, group = inp
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
            cfg, shared["attn"], apply_norm(cfg, shared, "ln1", x3), cos, sin, ck, cvv,
            group, length,
        )
        x3, _ = _ffn(cfg, shared, x3 + h, ctx)
        return (x3[:, 0], ck, cvv), (jnp.stack(sts), jnp.stack(cvs))

    (x2, ck, cvv), (st, cv) = jax.lax.scan(
        body,
        (x2, cache["kv_k"], cache["kv_v"]),
        (params["blocks"], cache["ssm_state"], cache["conv"],
         jnp.arange(cache["kv_k"].shape[0])),
    )
    logits = _logits(cfg, params, x2[:, None])
    return logits, {
        "length": length + 1,
        "ssm_state": st,
        "conv": cv,
        "kv_k": ck,
        "kv_v": cvv,
    }


def _whisper_decode(cfg, params, cache, x, pos, ctx):
    length = cache["length"]
    pos_emb = jax.lax.dynamic_index_in_dim(params["dec_pos"], length, keepdims=True)
    x = x + pos_emb[None].astype(x.dtype)

    def body(carry, inp):
        xc, ck, cv = carry
        p, crk, crv, layer = inp
        h, ck, cv = attn.attention_decode(
            cfg, p["attn"], apply_norm(cfg, p, "ln1", xc), None, None, ck, cv, layer, length
        )
        xc = xc + h
        xc = xc + attn.cross_attention(
            cfg, p["cross"], apply_norm(cfg, p, "ln2", xc), crk, crv
        )
        xc = xc + mlp_forward(cfg, p["mlp"], apply_norm(cfg, p, "ln3", xc))
        return (xc, ck, cv), None

    (x, ck, cv), _ = jax.lax.scan(
        body,
        (x, cache["kv_k"], cache["kv_v"]),
        (
            params["dec_blocks"],
            cache["cross_k"],
            cache["cross_v"],
            jnp.arange(cfg.num_layers),
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


# ===========================================================================
# Families
# ===========================================================================


class _Family(NamedTuple):
    """One family's implementation of each entry point of this module."""

    params: Callable  # (cfg, keys, dtype) -> the stacks beside embed/final/head
    cache: Callable  # (cfg, batch, max_len, enc_len, dtype) -> cache arrays
    forward: Callable  # (cfg, params, batch, ctx, want_cache, cache_len)
    decode: Callable  # (cfg, params, cache, x, pos, ctx) -> (logits, cache)
    no_prefix: Optional[str]  # why a stored K/V prefix cannot enter the cache


_DENSE = _Family(_dense_params, _dense_cache, _dense_forward, _dense_decode, None)
_FAMILIES: Dict[str, _Family] = {
    "dense": _DENSE,
    "moe": _DENSE,
    "vlm": _DENSE,
    "mla": _Family(_mla_params, _mla_cache, _mla_forward, _mla_decode,
                   "a latent (MLA) cache holds kv_latent, where the page pool "
                   "holds per-head kv_k/kv_v"),
    "ssm": _Family(_rwkv_params, _rwkv_cache, _rwkv_forward, _rwkv_decode,
                   "an RWKV cache carries recurrent state, which a stored "
                   "prefix cannot be re-entered into mid-stream"),
    "hybrid": _Family(_hybrid_params, _hybrid_cache, _hybrid_forward, _hybrid_decode,
                      "a hybrid (Mamba) cache carries recurrent state, which a "
                      "stored prefix cannot be re-entered into mid-stream"),
    "audio": _Family(_whisper_params, _whisper_cache, _whisper_forward, _whisper_decode,
                     "an encoder-decoder cache carries its encoder's "
                     "cross-attention K/V"),
}


def _family(cfg) -> _Family:
    """The one place a config's path is chosen: latent attention
    (DeepSeek-V2) by ``cfg.mla``, every other stack by ``cfg.family``."""
    return _FAMILIES["mla" if cfg.mla is not None else cfg.family]
