"""Plain reference of a dense decoder-only transformer, and its fp8 control.

Written from the published architectures, not from the program: pre-norm
blocks (RMSNorm with scale, or OLMo's non-parametric LayerNorm), grouped
query attention with optional q/k/v biases and Qwen3's per-head RMSNorm on
queries and keys, rotary embeddings over split halves, causal softmax, a
SwiGLU MLP, and an output head that is the embedding (tied) or its own
matrix. It reads the benchmark's weights by the program's parameter names,
and nothing else of the program.

The reference computes in float32 with every matrix product at ``highest``
precision. The control is the same computation with each matrix product's
inputs rounded to float8 e4m3, weights with one scale per output column and
activations with one scale per row: the precision a later change could be
tempted to serve in. The control runs only when ``bench.harness.run`` is
asked for it (``bench/calibrate.py`` and the tests), never in a benchmark
run.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _q8(a, axis):
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q, scale


def _mm(x, w, control):
    w = w.astype(F32)
    if not control:
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, sx = _q8(x, -1)
    wq, sw = _q8(w, 0)
    # e4m3 values are exact in bfloat16; products accumulate in float32
    y = jnp.matmul(xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
                   preferred_element_type=F32)
    return y * sx * sw


def _norm(m, x, scale):
    if m["norm"] == "layernorm_np":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + m["norm_eps"])
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + m["norm_eps"])
    return x * scale.astype(F32)


def _rope(x, theta):
    T, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(T, dtype=F32)[:, None] * freq  # (T, half)
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _layer(m, control, x, p):
    B, T, D = x.shape
    H, K, hd = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    a = p["attn"]
    h = _norm(m, x, p.get("ln1_scale"))
    q, k, v = _mm(h, a["wq"], control), _mm(h, a["wk"], control), _mm(h, a["wv"], control)
    if m["attention_bias"]:
        q, k, v = q + a["bq"].astype(F32), k + a["bk"].astype(F32), v + a["bv"].astype(F32)
    q, k, v = q.reshape(B, T, H, hd), k.reshape(B, T, K, hd), v.reshape(B, T, K, hd)
    if m["qk_norm"]:
        q = _norm({**m, "norm": "rmsnorm"}, q, a["q_norm_scale"])
        k = _norm({**m, "norm": "rmsnorm"}, k, a["k_norm_scale"])
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(F32(hd))
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision=HIGHEST)
    x = x + _mm(o.reshape(B, T, H * hd), a["wo"], control)
    h = _norm(m, x, p.get("ln2_scale"))
    f = p["mlp"]
    g = jax.nn.silu(_mm(h, f["w_gate"], control)) * _mm(h, f["w_up"], control)
    return x + _mm(g, f["w_down"], control), None


def _hidden(m, control, params, tokens):
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    x, _ = jax.lax.scan(partial(_layer, m, control), x, params["blocks"])
    return _norm(m, x, params.get("final_scale"))


def _head(m, params):
    return params["embed"].T if m["tie_word_embeddings"] else params["head"]


@partial(jax.jit, static_argnames=("spec", "chunk"))
def _reference(params, tokens, served, alt, *, spec, chunk):
    """Per position: the best logit, and the logits of two given tokens."""
    m = dict(spec)
    h = _hidden(m, False, params, tokens)
    B, T, D = h.shape
    w = _head(m, params)

    def one(args):
        hc, sc, ac = args
        lg = _mm(hc, w, False)  # (B, C, V)
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
    m = dict(spec)
    h = _hidden(m, True, params, tokens)
    B, T, D = h.shape
    w = _head(m, params)

    def one(hc):
        return jnp.argmax(_mm(hc, w, True), -1).astype(jnp.int32)

    hs = jnp.moveaxis(h.reshape(B, T // chunk, chunk, D), 1, 0)
    return jnp.moveaxis(jax.lax.map(one, hs), 0, 1).reshape(B, T)


def _spec(m: dict):
    return tuple(sorted((k, v) for k, v in m.items() if not isinstance(v, (list, dict))))


def reference(m: dict, params, tokens, served, alt, chunk: int = 128):
    """(best, at served, at alt) logits per position, float32 reference."""
    return _reference(params, tokens, served, alt, spec=_spec(m), chunk=chunk)


def control_argmax(m: dict, params, tokens, chunk: int = 128):
    return _control(params, tokens, spec=_spec(m), chunk=chunk)
