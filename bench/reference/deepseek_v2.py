"""Plain reference of DeepSeek-V2 (latent attention, DeepSeekMoE), and its
fp8 control.

Written from the DeepSeek-V2 paper (arXiv:2405.04434) and the published
config.json, not from the program: pre-norm blocks with RMSNorm; multi-head
latent attention in its expanded form (per-head keys [k_nope, k_pe] and
values expanded from the normed latent, the rotary key shared by all
heads), YaRN rotary frequencies over the rotary part, rotated as the
published modeling code does (de-interleave, then rotate halves), and the
softmax scale with YaRN's mscale squared; the first
``first_k_dense_replace`` layers with a dense SwiGLU MLP, the rest
DeepSeekMoE: softmax router, greedy top-k, weights renormalised only with
``norm_topk_prob``, and the shared experts as one SwiGLU MLP added for
every token. A file that asks for another router (``scoring_func``,
``topk_method``), a ``routed_scaling_factor`` other than 1 or a low-rank
query projection (``q_lora_rank``) is refused: neither this reference nor
the program implements them. Every token passes through all
routed experts, with a gate of zero outside its top-k. The output head is
its own matrix. It reads the benchmark's weights by the program's
parameter names, and takes every width from the configuration file.

The reference computes in float32 with every matrix product at ``highest``
precision, one layer at a time and one expert at a time, so it fits beside
the weights once the program is freed. The control rounds each weight
product's inputs to float8 e4m3 with ``dense_lm``'s scales.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_lm import F32, HIGHEST, _mm


def _rms(m, x, scale):
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + m["rms_norm_eps"])
    return x * scale.astype(F32)


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_cos_sin(m, T):
    """cos, sin (T, rope_dim) of the published YaRN rotary embedding."""
    y, dim, base = m["rope_scaling"], m["qk_rope_head_dim"], float(m["rope_theta"])
    orig = y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inter = 1.0 / (y["factor"] * base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / max(high - low, 0.001), 0, 1)
    mask = 1.0 - ramp
    inv = inter * (1 - mask) + extra * mask
    freqs = np.outer(np.arange(T, dtype=np.float32), inv.astype(np.float32))
    emb = np.concatenate([freqs, freqs], -1)
    ms = _yarn_mscale(y["factor"], y["mscale"]) / _yarn_mscale(y["factor"], y["mscale_all_dim"])
    return jnp.asarray(np.cos(emb) * ms, F32), jnp.asarray(np.sin(emb) * ms, F32)


def _rope(x, cos, sin):
    """x (B, T, h, d): de-interleave the pairs, then rotate halves."""
    d = x.shape[-1]
    x = x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(x.shape)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], -1)
    return x * c + rot * s


def _attention(m, control, x, p):
    B, T, D = x.shape
    H = m["num_attention_heads"]
    nope, rope, vd, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
                         m["kv_lora_rank"])
    a = p["attn"]
    h = _rms(m, x, p["ln1_scale"])
    q = _mm(h, a["wq"], control).reshape(B, T, H, nope + rope)
    kv = _mm(h, a["wkv_a"], control)
    c = _rms(m, kv[..., :r], a["kv_norm_scale"])
    kvb = _mm(c, a["wkv_b"], control).reshape(B, T, H, nope + vd)
    cos, sin = _yarn_cos_sin(m, T)
    q_pe = _rope(q[..., nope:], cos, sin)
    k_pe = _rope(kv[:, :, None, r:], cos, sin)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(k_pe, (B, T, H, rope))], -1)
    scale = (nope + rope) ** -0.5 * _yarn_mscale(m["rope_scaling"]["factor"],
                                                  m["rope_scaling"]["mscale_all_dim"]) ** 2
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * scale
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kvb[..., nope:], precision=HIGHEST)
    return x + _mm(o.reshape(B, T, H * vd), a["wo"], control)


def _swiglu(control, h, f):
    return _mm(jax.nn.silu(_mm(h, f["w_gate"], control)) * _mm(h, f["w_up"], control),
               f["w_down"], control)


def _dense_layer(m, control, x, p):
    x = _attention(m, control, x, p)
    return x + _swiglu(control, _rms(m, x, p["ln2_scale"]), p["mlp"]), None


def _moe_layer(m, control, x, p):
    x = _attention(m, control, x, p)
    return x + _moe(m, control, _rms(m, x, p["ln2_scale"]), p["moe"]), None


def _moe(m, control, h, e):
    """DeepSeekMoE over h (B, T, D): every routed expert over every token,
    gated by zero outside the token's top-k, plus the shared experts."""
    probs = jax.nn.softmax(_mm(h, e["router"], control), -1)  # (B, T, E)
    top, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    gates = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=F32) * top[..., None], -2)
    if m["norm_topk_prob"]:
        gates = gates / jnp.sum(gates, -1, keepdims=True)

    def expert(y, inp):  # one routed expert over every token
        wg, wu, wd, g = inp
        f = {"w_gate": wg, "w_up": wu, "w_down": wd}
        return y + g[..., None] * _swiglu(control, h, f), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (e["w_gate"], e["w_up"], e["w_down"], jnp.moveaxis(gates, -1, 0)))
    if m["n_shared_experts"]:
        y = y + _swiglu(control, h, e["shared"])
    return y


def _hidden(m, control, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    if m["first_k_dense_replace"]:
        x, _ = jax.lax.scan(partial(_dense_layer, m, control), x, params["dense_blocks"])
    x, _ = jax.lax.scan(partial(_moe_layer, m, control), x, params["blocks"])
    return _rms(m, x, params["final_scale"])


@partial(jax.jit, static_argnames=("spec", "chunk"))
def _reference(params, tokens, served, alt, *, spec, chunk):
    """Per position: the best logit, and the logits of two given tokens."""
    m = _unspec(spec)
    h = _hidden(m, False, params, tokens)
    B, T, D = h.shape

    def one(args):
        hc, sc, ac = args
        lg = _mm(hc, params["head"], False)  # (B, C, V)
        return (jnp.max(lg, -1),
                jnp.take_along_axis(lg, sc[..., None], -1)[..., 0],
                jnp.take_along_axis(lg, ac[..., None], -1)[..., 0])

    def split(a):
        return jnp.moveaxis(a.reshape(B, T // chunk, chunk, *a.shape[2:]), 1, 0)

    outs = jax.lax.map(one, (split(h), split(served), split(alt)))
    return tuple(jnp.moveaxis(o, 0, 1).reshape(B, T) for o in outs)


@partial(jax.jit, static_argnames=("spec", "chunk"))
def _control(params, tokens, *, spec, chunk):
    """Per position: the token the fp8 control puts first."""
    m = _unspec(spec)
    h = _hidden(m, True, params, tokens)
    B, T, D = h.shape

    def one(hc):
        return jnp.argmax(_mm(hc, params["head"], True), -1).astype(jnp.int32)

    hs = jnp.moveaxis(h.reshape(B, T // chunk, chunk, D), 1, 0)
    return jnp.moveaxis(jax.lax.map(one, hs), 0, 1).reshape(B, T)


# published keys with the one value this reference (and the program) serves
FIXED = {"scoring_func": "softmax", "topk_method": "greedy", "routed_scaling_factor": 1,
         "q_lora_rank": None}


def _spec(m: dict):
    """The file's model entry as a hashable jit key (``rope_scaling`` flat)."""
    other = {k: m[k] for k, v in FIXED.items() if m[k] != v}
    if other:
        raise NotImplementedError(f"DeepSeek-V2 variant not implemented: {other}")
    flat = {k: v for k, v in m.items() if not isinstance(v, (list, dict))}
    flat.update({f"rope_scaling.{k}": v for k, v in m["rope_scaling"].items()})
    return tuple(sorted(flat.items()))


def _unspec(spec) -> dict:
    m = {k: v for k, v in spec if not k.startswith("rope_scaling.")}
    m["rope_scaling"] = {k.split(".", 1)[1]: v for k, v in spec if k.startswith("rope_scaling.")}
    return m


def reference(m: dict, params, tokens, served, alt, chunk: int = 128):
    """(best, at served, at alt) logits per position, float32 reference."""
    with jax.default_matmul_precision("highest"):
        return _reference(params, tokens, served, alt, spec=_spec(m), chunk=chunk)


def control_argmax(m: dict, params, tokens, chunk: int = 128):
    with jax.default_matmul_precision("highest"):
        return _control(params, tokens, spec=_spec(m), chunk=chunk)
