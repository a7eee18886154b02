"""DeepSeek-V2 (latent attention, DeepSeekMoE) against its float32
reference at a small size on seeded random weights, and the pieces that
make it up: YaRN, absorbed against expanded attention, the routed-gather
and grouped expert paths, and the engine's handling of the latent cache."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import deploy
from bench.reference import deepseek_v2 as ref
from repro.configs import registry
from repro.models import attention as attn
from repro.models import lm
from repro.models import moe as moe_mod
from repro.models.common import mlp_forward
from repro.models.rope import yarn_angles, yarn_inv_freq, yarn_softmax_scale
from repro.serving.engine import Engine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = "deepseek-v2-lite"
SEED = 2**33 + 16
FILE = os.path.join(ROOT, "bench", "configs", "apc-1chip-deepseek-v2-lite.json")


def _file_entry():
    with open(FILE) as f:
        return json.load(f)["models"][ARCH]


def _small(dtype="float32", layers=3, experts=8, top_k=3):
    """(file-style spec for the reference, program config): the benchmark
    file's model cut in every width, the two built from the same numbers."""
    spec = dict(_file_entry(), num_hidden_layers=layers, hidden_size=64,
                num_attention_heads=4, head_dim=16, intermediate_size=96,
                vocab_size=512, dtype=dtype, kv_lora_rank=32, qk_nope_head_dim=16,
                qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=experts,
                num_experts_per_tok=top_k, moe_intermediate_size=32)
    cfg = deploy.model_config(spec)
    cfg = dataclasses.replace(
        cfg,
        mla=dataclasses.replace(cfg.mla, kv_lora_rank=32, qk_nope_head_dim=16,
                                qk_rope_head_dim=8, v_head_dim=16),
        moe=dataclasses.replace(cfg.moe, num_experts=experts, experts_per_token=top_k,
                                d_ff_expert=32))
    return spec, cfg


def _ref_logits(spec, params, tokens):
    with jax.default_matmul_precision("highest"):
        h = ref._hidden(ref._unspec(ref._spec(spec)), False, params, jnp.asarray(tokens))
        return np.asarray(jnp.matmul(h, params["head"].astype(jnp.float32),
                                     precision=jax.lax.Precision.HIGHEST))


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.abs(a - b).max() / np.abs(b).max())


def _prefill_then_decode(cfg, params, tokens, S):
    """Logits of prefill over tokens[:, :S], then of each decode step fed
    the rest through the latent cache: (B, T, V) like a full forward."""
    T = tokens.shape[1]
    logits, cache = lm.prefill(cfg, params, {"tokens": jnp.asarray(tokens[:, :S])},
                               cache_len=T + 2)
    out = [np.asarray(logits, np.float32)]
    for i in range(S, T):
        ld, cache = lm.decode_step(cfg, params, cache, jnp.asarray(tokens[:, i:i + 1]))
        out.append(np.asarray(ld, np.float32))
    return np.concatenate(out, axis=1), cache


def test_prefill_and_cached_decode_match_the_reference():
    """Prefill logits, then logits decoded through the latent cache (the
    absorbed form), against the reference's full forward pass. The program
    computes in float32 here, as the reference does: what differs is the
    order of sums, the absorbed products and the routed experts' grouping,
    about 1e-6 of the largest logit, so 1e-4 leaves room. The same program
    in bfloat16 lies above 1e-3, so a lower precision than the
    configuration states fails it."""
    spec, cfg = _small()
    params = deploy.make_params(cfg, SEED, 0.05)
    tokens = np.random.default_rng(0).integers(0, 512, (2, 40)).astype(np.int32)
    want = _ref_logits(spec, params, tokens)
    got, cache = _prefill_then_decode(cfg, params, tokens, 24)
    assert _rel(got[:, :24], want[:, :24]) < 1e-4
    assert _rel(got[:, 24:], want[:, 24:]) < 1e-4
    assert int(cache["length"]) == 40

    _, cfg16 = _small("bfloat16")
    low, _ = _prefill_then_decode(cfg16, deploy.make_params(cfg16, SEED, 0.05), tokens, 24)
    assert _rel(low, want) > 1e-3


def test_absorbed_decode_equals_the_expanded_form():
    _, cfg = _small()
    p = attn.mla_init(cfg, jax.random.PRNGKey(1))
    p = jax.tree.map(lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(2), a.shape), p)
    S, M = 13, 20
    x = jax.random.normal(jax.random.PRNGKey(3), (1, S, cfg.d_model))
    pos = jnp.arange(S)[None]
    cos, sin = yarn_angles(pos, cfg.mla.qk_rope_head_dim, cfg.rope_theta, cfg.yarn)
    out_seq, rows = attn.mla_attention_seq(cfg, p, x, cos, sin)
    cache = jnp.zeros((2, 1, M, cfg.mla.cache_dim)).at[1, :, : S - 1].set(rows[:, : S - 1])
    out_dec, cache = attn.mla_attention_decode(
        cfg, p, x[:, S - 1:], cos[:, S - 1:], sin[:, S - 1:], cache, 1, jnp.int32(S - 1))
    np.testing.assert_allclose(np.asarray(out_dec[:, 0]), np.asarray(out_seq[:, -1]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(cache[1, :, :S]), np.asarray(rows), rtol=1e-6)
    assert not np.asarray(cache[0]).any() and not np.asarray(cache[1, :, S:]).any()


@pytest.mark.parametrize("tokens", [2, 16], ids=["routed-gather", "grouped"])
def test_expert_paths_equal_the_references_all_experts_form(tokens):
    """Two tokens route 6 pairs to 8 experts (the gather path); sixteen
    route 48 (the grouped path). Both, and each path called directly, equal
    the reference's every-expert form with gates zero outside the top-k."""
    spec, cfg = _small()
    p = deploy.make_params(cfg, SEED, 0.2)["blocks"]["moe"]
    p = jax.tree.map(lambda a: a[0], p)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, tokens, cfg.d_model))
    y, _, counts = moe_mod.moe_forward_dropless(cfg, p, x)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref._moe(ref._unspec(ref._spec(spec)), False, x, p))
    # float32 on both sides, summed in other orders: outputs of unit scale
    # agree to about 1e-6
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-5, atol=1e-5)
    _, w, idx, _ = moe_mod._routing(cfg, p, x[0])
    shared = np.asarray(mlp_forward(cfg, p["shared"], x[0]))
    for path in (moe_mod._routed_gather, moe_mod._routed_grouped):
        got = np.asarray(path(cfg, p, x[0], w, idx, None)) + shared
        np.testing.assert_allclose(got, want[0], rtol=1e-5, atol=1e-5)
    assert counts.tolist() == np.bincount(np.asarray(idx).ravel(), minlength=8).tolist()
    assert int(counts.sum()) == tokens * 3


@pytest.mark.parametrize("tokens", [2, 16], ids=["routed-gather", "grouped"])
def test_a_layer_of_the_stack_equals_the_layer_alone(tokens):
    """With ``layer``, the expert weights are the whole stack and the path
    reads that layer's experts from it."""
    _, cfg = _small(layers=4)
    stack = deploy.make_params(cfg, SEED, 0.2)["blocks"]["moe"]
    one = jax.tree.map(lambda a: a[2], stack)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, tokens, cfg.d_model))
    with_stack = {**one, **{n: stack[n] for n in moe_mod.EXPERT_WEIGHTS}}
    y_stack, _, c_stack = moe_mod.moe_forward_dropless(cfg, with_stack, x, layer=jnp.int32(2))
    y_one, _, c_one = moe_mod.moe_forward_dropless(cfg, one, x)
    np.testing.assert_allclose(np.asarray(y_stack), np.asarray(y_one), rtol=1e-6, atol=1e-7)
    assert c_stack.tolist() == c_one.tolist()


def test_weights_stay_unrenormalised_and_shared_experts_are_added():
    _, cfg = _small()
    p = jax.tree.map(lambda a: a[0], deploy.make_params(cfg, SEED, 0.2)["blocks"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 16, cfg.d_model))
    probs, w, idx, _ = moe_mod._routing(cfg, p, x[0])
    np.testing.assert_array_equal(np.asarray(w), np.take_along_axis(
        np.asarray(probs), np.asarray(idx), -1))
    assert float(jnp.max(jnp.sum(w, -1))) < 0.99
    y, _, _ = moe_mod.moe_forward_dropless(cfg, p, x)
    alone = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, num_shared_experts=0))
    y_routed, _, _ = moe_mod.moe_forward_dropless(alone, p, x)
    np.testing.assert_allclose(np.asarray(y - y_routed),
                               np.asarray(mlp_forward(cfg, p["shared"], x)), rtol=1e-5, atol=1e-6)


def test_yarn_at_the_published_numbers():
    c = registry.get(ARCH)
    base = 1.0 / 10_000.0 ** (np.arange(0, 64, 2) / 64)
    ratio = yarn_inv_freq(64, c.rope_theta, c.yarn) / base
    np.testing.assert_allclose(ratio[:11], 1.0, rtol=1e-6)
    np.testing.assert_allclose(ratio[23:], 1.0 / 40, rtol=1e-6)
    assert np.all(np.diff(ratio[10:24]) < 0)
    np.testing.assert_allclose(yarn_softmax_scale(192, c.yarn), 0.0722 * 1.2608 ** 2, rtol=1e-3)
    np.testing.assert_allclose(yarn_softmax_scale(192, c.yarn),
                               192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2, rtol=1e-12)


@pytest.mark.parametrize("arch", [a for a in registry.ARCH_NAMES
                                  if registry.get(a).moe is not None and a != ARCH])
def test_existing_moe_routing_is_unchanged_by_the_new_defaults(arch):
    cfg = registry.get_smoke(arch)
    p = moe_mod.moe_init(cfg, jax.random.PRNGKey(7))
    x2d = jax.random.normal(jax.random.PRNGKey(8), (24, cfg.d_model))
    _, w, idx, _ = moe_mod._routing(cfg, p, x2d)
    probs = jax.nn.softmax(x2d.astype(jnp.float32) @ p["router"], axis=-1)
    w0, idx0 = jax.lax.top_k(probs, cfg.moe.experts_per_token)
    w0 = w0 / jnp.maximum(jnp.sum(w0, axis=-1, keepdims=True), 1e-9)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w0))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx0))
    assert "shared" not in p


def test_the_registry_holds_every_key_the_benchmark_file_states():
    m, c = _file_entry(), registry.get(ARCH)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(x for x in json.load(f)["configs"] if x["file"].endswith(os.path.basename(FILE)))
    assert entry["reduced"] == ["num_hidden_layers"]
    assert (c.num_layers, m["num_hidden_layers"]) == (27, 7)
    y = m["rope_scaling"]
    pairs = {
        "hidden_size": c.d_model, "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads, "head_dim": c.head_dim,
        "intermediate_size": c.d_ff, "vocab_size": c.vocab_size, "rope_theta": c.rope_theta,
        "norm": c.norm_type, "attention_bias": c.qkv_bias, "qk_norm": c.qk_norm,
        "tie_word_embeddings": c.tie_embeddings, "activation": c.mlp_act, "dtype": c.dtype,
        "kv_lora_rank": c.mla.kv_lora_rank,
        "qk_nope_head_dim": c.mla.qk_nope_head_dim, "qk_rope_head_dim": c.mla.qk_rope_head_dim,
        "v_head_dim": c.mla.v_head_dim, "n_routed_experts": c.moe.num_experts,
        "num_experts_per_tok": c.moe.experts_per_token,
        "moe_intermediate_size": c.moe.d_ff_expert, "n_shared_experts": c.moe.num_shared_experts,
        "norm_topk_prob": c.moe.norm_topk_prob, "first_k_dense_replace": c.moe.first_k_dense,
    }
    for key, value in pairs.items():
        assert m[key] == value, key
    # published keys the program has no setting for: it serves only these values
    assert {k: m[k] for k in ref.FIXED} == ref.FIXED
    assert (y["factor"], y["original_max_position_embeddings"], y["beta_fast"], y["beta_slow"],
            y["mscale"], y["mscale_all_dim"]) == dataclasses.astuple(c.yarn)
    assert m["rms_norm_eps"] == m["norm_eps"] == 1e-6 and m["hidden_act"] == "silu"


def test_the_benchmark_file_top_level_repeats_the_model_block():
    # the top level states the published config.json as run; the harness and
    # the reference read the model block, so the two must not drift apart
    with open(FILE) as f:
        doc = json.load(f)
    m = doc["models"][ARCH]
    shared = [k for k in doc if k in m]
    assert len(shared) == 32 and "rope_scaling" in shared
    for key in shared:
        assert doc[key] == m[key], key


@pytest.mark.parametrize("key,value", [("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
                                       ("routed_scaling_factor", 2.5), ("q_lora_rank", 1536)])
def test_the_reference_refuses_a_variant_the_program_does_not_serve(key, value):
    m = {**_file_entry(), key: value}
    with pytest.raises(NotImplementedError, match=key):
        ref.reference(m, {}, None, None, None)


def _engine(**kw):
    cfg = registry.get_smoke(ARCH)
    return Engine(cfg, lm.init_params(cfg, jax.random.PRNGKey(9)), max_len=64, **kw)


def test_generate_makes_three_host_syncs_and_counts_routed_pairs():
    eng = _engine()
    counter = eng.obs.counter("engine.host_syncs")
    K, L_moe = eng.cfg.moe.experts_per_token, eng.cfg.num_layers - eng.cfg.moe.first_k_dense
    rng = np.random.default_rng(1)
    for S, n in ((9, 6), (20, 1), (3, 12)):
        before = counter.value
        out = eng.generate(rng.integers(3, 500, (1, S)).astype(np.int32), max_new=n)
        assert out.shape == (1, n)
        assert counter.value - before == (3 if n > 1 else 2)
    st = eng.stats
    assert st.expert_tokens.shape == (L_moe, eng.cfg.moe.num_experts)
    assert int(st.expert_tokens.sum()) == K * L_moe * (st.prefill_tokens + st.decode_tokens)
    counted = sum(c.value for row in eng._expert_counters for c in row)
    assert counted == int(st.expert_tokens.sum())


def test_the_prefix_path_refuses_a_latent_cache():
    from repro.serving.kv_cache import CachePoint

    eng = _engine()
    toks = np.arange(3, 15, dtype=np.int32)[None]
    _, cache = eng.prefill(toks)
    with pytest.raises(NotImplementedError, match="latent"):
        eng.register_prefix("t", cache, 8)
    with pytest.raises(NotImplementedError, match="latent"):
        eng.prefill_with_prefix("t", toks[:, 8:])
    with pytest.raises(NotImplementedError, match="latent"):
        eng.generate(toks, max_new=2, cache_point=CachePoint("t", 8))
    with pytest.raises(NotImplementedError, match="latent"):
        lm.prefill_extend(eng.cfg, eng.params, {"tokens": toks[:, 8:]},
                          cache["kv_latent"], cache["kv_latent"], 8)


def test_flash_attention_takes_a_value_head_of_its_own():
    """Prompts past 512 tokens prefill through the chunked flash path, with
    keys of qk_nope + qk_rope and values of v_head_dim, at MLA's scale."""
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(10), 3)
    q = jax.random.normal(kq, (1, 300, 4, 24))
    k = jax.random.normal(kk, (1, 300, 4, 24))
    v = jax.random.normal(kv, (1, 300, 4, 16))
    flash = attn.flash_attention_jnp(q, k, v, chunk=128, scale=0.11)
    naive = attn.naive_attention(q, k, v, scale=0.11)
    assert flash.shape == naive.shape == (1, 300, 4, 16)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(naive), rtol=1e-4, atol=1e-5)
