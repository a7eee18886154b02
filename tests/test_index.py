"""repro.index subsystem: kernel parity, bank invariants, LSH consistency.

Pallas ``batch_topk`` runs in interpret mode on this CPU container; the
parity sweep pins it to the numpy oracle (``ref.topk_cosine_ref``) on
scores (atol 1e-5) AND indices — drift here is the signal the CI smoke
workflow exists to catch.
"""

import threading

import numpy as np
import pytest

from repro.core.cache import PlanCache
from repro.core.fuzzy import FuzzyMatcher
from repro.index import DIM, EmbeddingBank, SimilarityIndex, embed, embed_batch
from repro.index.bucketed import BucketedIndex, _brute_topk

RNG = np.random.RandomState(7)


def _unit_rows(n, seed=0):
    m = np.random.RandomState(seed).randn(n, DIM).astype(np.float32)
    m /= np.maximum(np.linalg.norm(m, axis=1, keepdims=True), 1e-9)
    return m


# -- Pallas kernel vs numpy oracle -------------------------------------------


@pytest.mark.parametrize("n", [0, 1, 17, 1000])
@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("q", [1, 5])
def test_batch_topk_matches_ref(n, k, q):
    from repro.kernels import ops, ref

    queries = _unit_rows(q, seed=n * 10 + k)
    bank = _unit_rows(n, seed=n + 1)
    s, i = ops.batch_topk(queries, bank, k=k)
    rs, ri = ref.topk_cosine_ref(queries, bank, k)
    np.testing.assert_allclose(np.asarray(s), rs, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i), ri)


def test_batch_topk_nonsquare_blocks():
    """N and Q far from block multiples (forces the padding path)."""
    from repro.kernels import ops, ref

    queries = _unit_rows(130, seed=3)
    bank = _unit_rows(1025, seed=4)
    s, i = ops.batch_topk(queries, bank, k=4, block_q=64, block_n=256)
    rs, ri = ref.topk_cosine_ref(queries, bank, 4)
    np.testing.assert_allclose(np.asarray(s), rs, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i), ri)


@pytest.mark.parametrize("k", [3, 8])
def test_batch_topk_ties_resolve_to_lowest_row(k):
    """Equal scores pick the lowest bank row, inside one block and across
    blocks (carried entries vs the current tile). Rows are signed axis
    vectors or zero (the embedding of an empty text), so every score is
    exactly -1, 0 or 1 and ties are exact."""
    from repro.kernels import ops, ref

    n = 700  # six 128-row blocks
    e = np.eye(DIM, dtype=np.float32)
    bank = np.tile(-e[0], (n, 1))
    bank[[5, 40, 130, 300, 699]] = e[0]  # two in block 0, then one each
    bank[[3, 200, 500, 650]] = 0.0
    bank[[7, 129, 640]] = e[1]
    queries = np.stack([e[0], -e[0], e[1], np.zeros(DIM, np.float32)])
    s, i = ops.batch_topk(queries, bank, k=k, block_q=8, block_n=128)
    rs, ri = ref.topk_cosine_ref(queries, bank, k)
    np.testing.assert_array_equal(np.asarray(s), rs)
    np.testing.assert_array_equal(np.asarray(i), ri)
    # spelled out: the five +e0 rows, then score-0 rows by row number
    np.testing.assert_array_equal(
        np.asarray(i)[0], [5, 40, 130, 300, 699, 3, 7, 129][:k])


# -- batched embedding --------------------------------------------------------


def test_embed_batch_matches_single():
    texts = ["working capital ratio", "net revenue 2023", "", "mean calculation"]
    batch = embed_batch(texts)
    for r, t in enumerate(texts):
        np.testing.assert_array_equal(batch[r], embed(t))
    norms = np.linalg.norm(batch, axis=1)
    assert norms[2] == 0.0  # empty text -> zero row, not NaN
    np.testing.assert_allclose(norms[[0, 1, 3]], 1.0, atol=1e-6)


# -- EmbeddingBank invariants -------------------------------------------------


def test_bank_add_remove_freelist_reuse():
    b = EmbeddingBank(initial_capacity=2)
    s0 = b.add("alpha")
    s1 = b.add("beta")
    s2 = b.add("gamma")  # forces growth
    assert len(b) == 3 and {s0, s1, s2} == {0, 1, 2}
    assert b.add("alpha") == s0  # idempotent re-add
    b.remove("beta")
    assert len(b) == 2 and b.key_of(s1) is None
    assert np.all(b.matrix()[s1] == 0.0)  # tombstoned row scores 0
    assert b.add("delta") == s1  # freelist reuses the freed slot
    assert b.key_of(s1) == "delta"
    np.testing.assert_array_equal(b.vector("delta"), embed("delta"))


def test_bank_random_ops_consistent_with_dict():
    b = EmbeddingBank(initial_capacity=4)
    model = {}
    for step in range(300):
        key = f"key-{RNG.randint(40)}"
        if RNG.rand() < 0.6:
            b.add(key)
            model[key] = True
        else:
            b.remove(key)
            model.pop(key, None)
        assert len(b) == len(model)
    assert sorted(b.keys()) == sorted(model)
    for k in model:
        slot = b.slot_of(k)
        assert b.key_of(slot) == k
        np.testing.assert_array_equal(b.matrix()[slot], embed(k))


# -- BucketedIndex ------------------------------------------------------------


def test_bucketed_finds_exact_entry_and_tracks_removal():
    bank = EmbeddingBank()
    idx = BucketedIndex(bank, n_bits=10, scan_threshold=0)
    keys = [f"intent keyword number {i}" for i in range(50)]
    for k in keys:
        idx.on_add(bank.add(k), bank.vector(k))
    q = embed(keys[17])
    scores, slots = idx.topk(q[None], k=1)
    assert bank.key_of(int(slots[0, 0])) == keys[17]
    assert scores[0, 0] == pytest.approx(1.0, abs=1e-6)
    # removal drops it from its bucket: the same probe can't return it
    idx.on_remove(bank.remove(keys[17]))
    _, slots = idx.topk(q[None], k=1)
    assert slots[0, 0] == -1 or bank.key_of(int(slots[0, 0])) != keys[17]


def test_bucketed_fallback_matches_brute_below_threshold():
    bank = EmbeddingBank()
    idx = BucketedIndex(bank, n_bits=8, scan_threshold=10_000)
    M = _unit_rows(300, seed=9)
    for i in range(300):
        idx.on_add(bank.add(f"k{i}", M[i]), M[i])
    q = _unit_rows(4, seed=11)
    s_idx, i_idx = idx.topk(q, k=3)
    s_ref, i_ref = _brute_topk(bank.matrix(), q, 3)
    np.testing.assert_allclose(s_idx, s_ref, atol=1e-6)
    np.testing.assert_array_equal(i_idx, i_ref)


def test_bucketed_slot_reuse_rehashes_signature():
    bank = EmbeddingBank()
    idx = BucketedIndex(bank, n_bits=12, scan_threshold=0)
    slot = bank.add("first key about revenue")
    idx.on_add(slot, bank.vector("first key about revenue"))
    idx.on_remove(bank.remove("first key about revenue"))
    slot2 = bank.add("completely different topic entirely")
    assert slot2 == slot  # freelist reuse
    idx.on_add(slot2, bank.vector("completely different topic entirely"))
    q = embed("completely different topic entirely")
    _, slots = idx.topk(q[None], k=1)
    assert bank.key_of(int(slots[0, 0])) == "completely different topic entirely"


# -- SimilarityIndex facade (all backends agree) ------------------------------


@pytest.mark.parametrize("backend", ["brute", "pallas", "bucketed", "auto"])
def test_similarity_index_backends_agree(backend):
    idx = SimilarityIndex(backend=backend)
    keys = [f"intent keyword number {i}" for i in range(40)]
    for k in keys:
        idx.add(k)
    assert idx.best_match("intent keyword number 7", threshold=0.8) == keys[7]
    assert idx.best_match("zz qq xx totally unrelated", threshold=0.99) is None
    idx.remove(keys[7])
    got = idx.best_match("intent keyword number 7", threshold=0.99)
    assert got != keys[7]
    batch = idx.best_match_batch(
        ["intent keyword number 3", "intent keyword number 12"], threshold=0.8
    )
    assert batch == [keys[3], keys[12]]


def test_similarity_index_topk_never_returns_tombstones():
    idx = SimilarityIndex(backend="brute")
    for kw in ("alpha beta", "gamma delta", "epsilon zeta"):
        idx.add(kw)
    idx.remove("gamma delta")
    # query anti-correlated with everything: the freed zero row would
    # rank first at score 0.0 if not masked
    q = -idx.bank.vector("alpha beta")
    scores, slots = idx.topk(q.reshape(1, -1), k=3)
    for c in range(3):
        assert slots[0, c] == -1 or idx.bank.key_of(int(slots[0, c])) is not None
        if slots[0, c] == -1:
            assert scores[0, c] <= -1e29


def test_pallas_backend_does_not_retrace_per_insert():
    from repro.kernels import ops

    before = ops.batch_topk._cache_size()
    idx = SimilarityIndex(backend="pallas", initial_capacity=64)
    for i in range(5):  # stays within one arena capacity
        idx.add(f"key number {i}")
        idx.best_match("key number 0", threshold=0.8)
    assert ops.batch_topk._cache_size() - before <= 1


# -- FuzzyMatcher / PlanCache integration ------------------------------------


def test_fuzzy_matcher_compat_keys_argument():
    m = FuzzyMatcher()
    m.add("stale key")
    # external key-set reconciliation (seed API): stale removed, new added
    assert m.best_match("fresh key", ["fresh key"], threshold=0.9) == "fresh key"
    assert m.best_match("stale key", threshold=0.99) != "stale key"


def test_plan_cache_ttl_expiry_keeps_index_in_sync():
    from repro.sim.clock import VirtualClock

    clock = VirtualClock()
    c = PlanCache(capacity=10, fuzzy=True, fuzzy_threshold=0.7, ttl_s=2.0,
                  clock=clock)
    c.insert("net profit margin analysis", 1)
    assert c.lookup("net profit margin analysis") == 1
    assert len(c._matcher.index) == 1
    clock.advance(2.1)
    assert c.lookup("net profit margin analysis") is None  # expired
    # the expired key must be gone from the fuzzy index too, not just _store
    assert len(c._matcher.index) == 0


def test_plan_cache_lookup_batch_mixed_hits():
    c = PlanCache(capacity=10, fuzzy=True, fuzzy_threshold=0.7)
    c.insert("working capital ratio", "wc")
    c.insert("net revenue growth", "nr")
    out = c.lookup_batch(
        ["working capital ratio",          # exact hit
         "working capital ratio analysis", # fuzzy hit
         "quantum chromodynamics"]         # miss
    )
    assert out == ["wc", "wc", None]
    assert c.stats.hits == 2 and c.stats.misses == 1


def test_plan_cache_concurrent_fuzzy_ops_stay_consistent():
    c = PlanCache(capacity=32, fuzzy=True, fuzzy_threshold=0.8)
    errors = []

    def writer(tid):
        try:
            for i in range(120):
                c.insert(f"keyword {tid} number {i}", i)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader():
        try:
            for i in range(200):
                c.lookup(f"keyword 0 number {i % 120}")
                len(c)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(t,)) for t in range(3)]
    threads += [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert len(c) <= 32
    # index and store agree exactly after the storm
    assert sorted(c._matcher.index.bank.keys()) == sorted(c.keys())


# -- LSH auto-tuning (telemetry loop closed) ----------------------------------


def _bank_of(vectors):
    bank = EmbeddingBank(initial_capacity=len(vectors))
    idx_slots = []
    with bank.lock:
        for i, v in enumerate(vectors):
            idx_slots.append(bank.add(f"k{i}", v))
    return bank, idx_slots


def test_lsh_autotune_converges_on_drifting_workload():
    """A workload that drifts 10x larger drives avg_candidates up; periodic
    autotune grows n_bits until candidates fall back under target, then
    goes quiet (converged)."""
    rng = np.random.RandomState(0)
    vectors = _unit_rows(6000, seed=1)
    bank = EmbeddingBank(initial_capacity=64)
    idx = BucketedIndex(bank, n_tables=4, n_bits=6, probe_hamming=1,
                        scan_threshold=0, recall_sample_every=0)

    def grow_to(n, start):
        with bank.lock:
            for i in range(start, n):
                slot = bank.add(f"k{i}", vectors[i])
                idx.on_add(slot, vectors[i])

    queries = _unit_rows(80, seed=2)
    actions = []
    sizes = [500, 2000, 6000]
    prev = 0
    for size in sizes:  # the drift: the bank keeps growing
        grow_to(size, prev)
        prev = size
        for _ in range(6):  # tuning windows per phase
            for q in queries:
                idx.best_slot(q)
            act = idx.autotune(target_candidates=96, min_queries=50)
            if act is None:
                break
            actions.append(act)

    assert actions, "autotune never acted on a 10x drift"
    assert idx.n_bits > 6  # candidate pressure grew the tables
    # converged: a fresh window triggers no further action and candidate
    # cost is back near target
    for q in queries:
        idx.best_slot(q)
    assert idx.autotune(target_candidates=96, min_queries=50) is None
    snap = idx.telemetry.snapshot()
    assert snap["avg_candidates"] <= 96 * 2


def test_lsh_autotune_widens_probe_on_low_recall():
    """With one table and no multi-probe, sampled live recall is poor;
    autotune widens probe_hamming (masks-only, no rebuild) up to its cap."""
    vectors = _unit_rows(3000, seed=3)
    bank = EmbeddingBank(initial_capacity=4096)
    with bank.lock:
        slots = [bank.add(f"k{i}", v) for i, v in enumerate(vectors)]
    idx = BucketedIndex(bank, n_tables=1, n_bits=12, probe_hamming=0,
                        scan_threshold=0, recall_sample_every=1)
    queries = _unit_rows(120, seed=4)
    actions = []
    for _ in range(4):
        for q in queries:
            idx.best_slot(q)
        act = idx.autotune(min_queries=50)
        if act is None:
            break
        actions.append(act)
    assert actions[:1] == ["probe_hamming->1"]
    assert idx.probe_hamming >= 1  # telemetry drove the widening
    # geometry survived: probing still answers and masks match n_bits
    s, slot = idx.best_slot(queries[0])
    assert slot == -1 or 0 <= slot < 4096


def test_similarity_index_autotune_facade():
    idx = SimilarityIndex(backend="brute")
    assert idx.autotune() is None  # no LSH tables to tune
    idx2 = SimilarityIndex(backend="bucketed")
    assert idx2.autotune() is None  # thin window: no action, no crash


def test_plan_cache_autotune_reaches_fuzzy_stage():
    c = PlanCache(capacity=16, fuzzy=True, index_backend="bucketed")
    c.insert("net revenue growth", 1)
    assert c.autotune() == []  # thin window -> no actions, plumbing intact
