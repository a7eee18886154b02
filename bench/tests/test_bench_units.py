"""Unit tests of the benchmark's yardstick: percentiles, peaks, operation
and byte counts against hand counts, traffic generation, and the plain
plan-cache matcher."""

from __future__ import annotations

import collections
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import check, deploy, flops, stats, traffic  # noqa: E402
from bench.peaks import peaks  # noqa: E402

MODELS = {a: m for name in ("apc-1chip-qwen2.5", "apc-1chip-qwen3")
          for a, m in deploy.load(name)["models"].items()}


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 70, 301])
def test_percentile_is_exact(q, n):
    xs = np.random.default_rng(n).exponential(size=n)
    assert stats.percentile(xs, q) == pytest.approx(float(np.percentile(xs, q)), rel=1e-12)


def test_peaks_known_and_unknown():
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


# hand counts: every head is tied, as published (OLMo-1B 1.18B,
# Qwen2.5-3B 3.09B, Qwen3-4B 4.02B)
HAND_PARAMS = {
    # 16 x (4 x 2048^2 + 3 x 2048 x 8192) + 50304 x 2048
    "olmo-1b": 16 * (4 * 2048 * 2048 + 3 * 2048 * 8192) + 50304 * 2048,
    # 36 x (2 x 2048^2 + 2 x 2048 x 256 + 3 x 2048 x 11008 + biases 2560
    #       + norms 4096) + 151936 x 2048 + 2048
    "qwen2.5-3b": 36 * (2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008 + 2560 + 4096)
    + 151936 * 2048 + 2048,
    # 36 x (2 x 2560 x 4096 + 2 x 2560 x 1024 + 3 x 2560 x 9728 + qk norms
    #       256 + norms 5120) + 151936 x 2560 + 2560
    "qwen3-4b": 36 * (2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728 + 256 + 5120)
    + 151936 * 2560 + 2560,
}


@pytest.mark.parametrize("arch", sorted(HAND_PARAMS))
def test_param_counts_match_hand_counts(arch):
    assert flops.params(MODELS[arch]) == HAND_PARAMS[arch]


def test_param_counts_match_published_sizes():
    assert flops.params(MODELS["olmo-1b"]) == 1_176_764_416
    assert flops.params(MODELS["qwen2.5-3b"]) == 3_085_938_688
    assert flops.params(MODELS["qwen3-4b"]) == 4_022_468_096


@pytest.mark.parametrize("arch,kv", [("olmo-1b", 2 * 16 * 16 * 128 * 2),
                                     ("qwen2.5-3b", 2 * 36 * 2 * 128 * 2),
                                     ("qwen3-4b", 2 * 36 * 8 * 128 * 2)])
def test_kv_bytes_per_token(arch, kv):
    assert flops.kv_bytes_per_token(MODELS[arch]) == kv


def test_weight_bytes_olmo_by_hand():
    # bf16 matrices (the tied head is the embedding, read once), one
    # embedding row; no norm parameters
    assert flops.weight_bytes(MODELS["olmo-1b"]) == 2 * 1_176_764_416 + 2 * 2048


def test_weight_bytes_qwen3_by_hand():
    m = MODELS["qwen3-4b"]
    # the head once; of the embedding, one row
    mats = 36 * (2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728) + 151936 * 2560
    norms_f32 = (2 * 36 + 1) * 2560 + 2 * 36 * 128
    assert flops.weight_bytes(m) == 2 * (mats + 2560) + 4 * norms_f32


def test_flops_by_hand():
    m = MODELS["qwen2.5-3b"]
    per_token = 2 * (36 * (2 * 2048 * 2048 + 2 * 2048 * 256 + 3 * 2048 * 11008) + 151936 * 2048)
    assert flops.matmul_flops_per_token(m) == per_token == 6_171_394_048
    attn_per_key = 4 * 36 * 16 * 128
    assert flops.decode_flops(m, 100) == per_token + 100 * attn_per_key
    assert flops.prefill_flops(m, 3) == 3 * per_token + (1 + 2 + 3) * attn_per_key
    assert flops.generate_flops(m, 3, 3) == (flops.prefill_flops(m, 3)
                                             + flops.decode_flops(m, 4)
                                             + flops.decode_flops(m, 5))
    assert flops.decode_bytes(m, 100) == flops.weight_bytes(m) + 101 * 2 * 36 * 2 * 128 * 2


def test_zipf_counts_apportion_a_block():
    assert traffic.zipf_counts(8, 16, 1.1) == [6, 3, 2, 1, 1, 1, 1, 1]
    assert sum(traffic.zipf_counts(5, 37, 0.8)) == 37


@pytest.mark.parametrize("name", ["hot-templates", "cold-unique"])
def test_every_seed_serves_the_same_tasks_in_another_order(name):
    doc = traffic.load(name)
    p1, s1 = traffic.generate(doc, 1)
    p2, s2 = traffic.generate(doc, 2**33 + 12345)
    assert [t.id for t in p1] == [t.id for t in p2]
    assert sorted(t.id for t in s1) == sorted(t.id for t in s2)
    assert [t.id for t in s1] != [t.id for t in s2]
    by_id = {t.id: t for t in s1}
    for t in s2:
        assert (t.query, t.context) == (by_id[t.id].query, by_id[t.id].context)
    _, again = traffic.generate(doc, 1)
    assert [t.id for t in again] == [t.id for t in s1]


def test_spread_order_keeps_every_prefix_near_its_share():
    counts = traffic.zipf_counts(8, 16, 1.1)
    order = traffic.spread_order(counts)
    assert collections.Counter(order) == dict(enumerate(counts))
    for p in range(1, len(order) + 1):
        for i, c in enumerate(counts):
            assert abs(order[:p].count(i) - p * c / len(order)) < 1


def test_every_seed_sends_the_same_intents_in_the_same_places():
    """A window that closes inside a block does the same work under any
    seed: the intents (and so the rounds) of the stream's places repeat."""
    doc = traffic.load("hot-templates")
    streams = [traffic.generate(doc, seed)[1] for seed in (1, 2**33 + 12345)]
    assert [t.intent.keyword for t in streams[0]] == [t.intent.keyword for t in streams[1]]
    assert [t.id for t in streams[0]] != [t.id for t in streams[1]]


def test_hot_blocks_hold_the_zipf_counts():
    doc = traffic.load("hot-templates")
    _, stream = traffic.generate(doc, 7)
    counts = traffic.zipf_counts(len(doc["intents"]), doc["block"], doc["zipf_s"])
    want = {it["keyword"]: c for it, c in zip(doc["intents"], counts)}
    for b in range(doc["blocks"]):
        block = stream[b * doc["block"]:(b + 1) * doc["block"]]
        assert collections.Counter(t.intent.keyword for t in block) == want


def _simulated_hits(name, index_backend):
    from repro.core.agent_loop import PlanActAgent
    from repro.core.backends import SimulatedBackend

    doc = deploy.load("apc-1chip-qwen2.5")
    tr = traffic.load(name)
    prewarm, stream = traffic.generate(tr, 3)
    cache = deploy.prewarmed_cache(doc, tr, prewarm, index_backend=index_backend)
    cfg = deploy.agent_config(doc)
    cfg.index_backend = index_backend
    agent = PlanActAgent(SimulatedBackend(seed=tr["semantic_seed"]), deploy.ledger(), cfg,
                         cache=cache)
    return [agent.run_task(t) for t in stream]


def test_hot_stream_hits_every_task_some_through_the_fuzzy_stage():
    recs = _simulated_hits("hot-templates", "brute")
    assert all(r.hit for r in recs)
    canonical = {it["keyword"] for it in traffic.load("hot-templates")["intents"]}
    paraphrased = [r for r in recs if r.keyword not in canonical]
    assert 0 < len(paraphrased) < 0.1 * len(recs)


def test_cold_stream_never_hits_exact_or_fuzzy():
    recs = _simulated_hits("cold-unique", "brute")
    assert not any(r.hit for r in recs)
    assert len({r.keyword for r in recs}) == len(recs)


def test_plain_matcher_flags_a_wrong_answer():
    keys = ["working capital ratio", "net debt position"]
    good = [check.Lookup("working capital ratio", "working capital ratio", True, True),
            check.Lookup("compute working capital ratio", "working capital ratio", True, False),
            check.Lookup("sales per store", None, True, True),
            check.Lookup("sales per store", "sales per store", True, True)]
    assert check.cache_mismatches(keys, good, 0.8, True)[0] == 0
    wrong_hit = [check.Lookup("compute working capital ratio", "net debt position", True, False)]
    assert check.cache_mismatches(keys, wrong_hit, 0.8, True)[0] == 1
    missed = [check.Lookup("net debt position", None, True, True)]
    assert check.cache_mismatches(keys, missed, 0.8, True)[0] == 1
    not_admitted = [check.Lookup("sales per store", None, True, False)]
    assert check.cache_mismatches(keys, not_admitted, 0.8, True)[0] == 1


def test_embedding_copy_matches_the_program():
    from repro.index.bank import embed_batch

    words = ["dupont roe decomposition derivation", "total from museum visitor logs", ""]
    for w, row in zip(words, embed_batch(words)):
        assert np.allclose(check.embed(w), row, atol=1e-6)
