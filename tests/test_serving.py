"""Serving runtime: engine, continuous batching + hedging, two-tier router."""

import threading

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core.cache import PlanCache
from repro.models import lm
from repro.obs import MetricsRegistry
from repro.obs import names as N
from repro.serving.engine import Engine
from repro.serving.router import TierPool, TwoTierRouter
from repro.serving.sampler import sample_token
from repro.serving.scheduler import ContinuousBatcher, Request, SchedulerConfig


def test_engine_generate_and_rates(rng_key):
    """``EngineStats`` counts every prompt token once and one token per row
    for each decode step (the last token needs no step), with time on both."""
    cfg = registry.get_smoke("olmo-1b")
    params = lm.init_params(cfg, rng_key)
    eng = Engine(cfg, params, max_len=48)
    toks = np.random.RandomState(0).randint(3, 400, (3, 12)).astype(np.int32)
    out = eng.generate(toks, max_new=6)
    assert out.shape == (3, 6)
    st = eng.stats
    assert st.prefill_tokens == 3 * 12
    assert st.decode_tokens == 3 * 5
    assert st.prefill_s > 0 and st.decode_s > 0
    assert st.prefix_tokens_reused == 0


def test_engine_greedy_deterministic(rng_key):
    cfg = registry.get_smoke("qwen2.5-3b")
    params = lm.init_params(cfg, rng_key)
    eng = Engine(cfg, params, max_len=48)
    toks = np.random.RandomState(1).randint(3, 400, (2, 10)).astype(np.int32)
    a = eng.generate(toks, max_new=5)
    b = eng.generate(toks, max_new=5)
    np.testing.assert_array_equal(a, b)


def test_engine_pads_after_eos_and_counts_active_rows(rng_key):
    """Rows past their EOS must emit pad_id, not freshly sampled garbage,
    and must stop counting toward decode throughput."""
    cfg = registry.get_smoke("olmo-1b")
    params = lm.init_params(cfg, rng_key)
    eng = Engine(cfg, params, max_len=48)
    toks = np.random.RandomState(0).randint(3, 400, (3, 12)).astype(np.int32)
    # pick the greedy second token of row 0 as eos: row 0 finishes early
    # while other rows (usually) keep generating
    probe = eng.generate(toks, max_new=2)
    eos = int(probe[0, 1])
    d0 = eng.stats.decode_tokens
    out = eng.generate(toks, max_new=6, eos_id=eos, pad_id=1)
    for r in range(out.shape[0]):
        hits = np.where(out[r] == eos)[0]
        if hits.size:
            assert (out[r, hits[0] + 1 :] == 1).all(), f"row {r} post-EOS garbage"
    # decode_tokens counts only rows still generating: strictly fewer than
    # B * steps once any row finished before the last emitted step
    steps = out.shape[1] - 1
    finished_early = any(
        np.where(out[r] == eos)[0].size and np.where(out[r] == eos)[0][0] < steps
        for r in range(out.shape[0])
    )
    if finished_early:
        assert eng.stats.decode_tokens - d0 < out.shape[0] * steps


def test_engine_generate_rejects_over_capacity(rng_key):
    """prompt + max_new beyond max_len must fail loudly up front — the old
    ``max_len + 8`` slack let decode scribble past the cache end."""
    import pytest

    cfg = registry.get_smoke("olmo-1b")
    params = lm.init_params(cfg, rng_key)
    eng = Engine(cfg, params, max_len=32)
    toks = np.random.RandomState(0).randint(3, 400, (2, 12)).astype(np.int32)
    with pytest.raises(ValueError, match="max_len"):
        eng.generate(toks, max_new=21)
    out = eng.generate(toks, max_new=20)  # exact capacity is fine
    assert out.shape == (2, 20)


def test_engine_prefill_counts_only_valid_tokens(rng_key):
    """prefill_tokens must reflect real tokens, not the padded (B, S)
    rectangle, or a prefill rate read from ``EngineStats`` overstates
    prefill throughput."""
    cfg = registry.get_smoke("olmo-1b")
    params = lm.init_params(cfg, rng_key)
    eng = Engine(cfg, params, max_len=48)
    toks = np.random.RandomState(0).randint(3, 400, (2, 16)).astype(np.int32)
    toks[0, 10:] = 0  # right-padded row: 10 valid
    lengths = np.asarray([10, 16])
    eng.prefill(toks, n_valid=int(lengths.sum()))
    assert eng.stats.prefill_tokens == 26
    eng.generate(toks, max_new=4, prompt_lengths=lengths)
    assert eng.stats.prefill_tokens == 52


def _host_loop(eng, toks, max_new, temperature, seed):
    """Generation as a per-step host loop: each step's logits read to the
    host, a token drawn from them and handed back to the next step."""
    logits, cache = eng.prefill(toks)
    key = sub = jax.random.PRNGKey(seed)
    out = []
    for i in range(max_new):
        if i:
            key, sub = jax.random.split(key)
        tok = np.asarray(sample_token(logits, temperature, sub))
        out.append(tok)
        if i + 1 < max_new:
            logits, cache = eng.decode(cache, tok)
    return np.concatenate(out, axis=1)


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy", "temperature"])
@pytest.mark.parametrize("arch", ["olmo-1b", "rwkv6-3b"])
def test_generate_keeps_tokens_on_device_and_matches_host_loop(arch, temperature,
                                                               rng_key):
    """Without ``eos_id`` the tokens stay on the device between steps: a
    call waits on the device three times (the prefill, the first decode
    step, the final fetch) and serves exactly the tokens of the per-step
    host loop, seed for seed."""
    cfg = registry.get_smoke(arch)
    eng = Engine(cfg, lm.init_params(cfg, rng_key), max_len=48)
    toks = np.random.RandomState(2).randint(3, 400, (2, 9)).astype(np.int32)
    syncs = eng.obs.counter(N.ENGINE_HOST_SYNCS)
    for seed in (5, 6):
        s0 = syncs.value
        got = eng.generate(toks, max_new=7, temperature=temperature, seed=seed)
        assert syncs.value - s0 == 3
        assert got.shape == (2, 7) and got.dtype == np.int32
        np.testing.assert_array_equal(got, _host_loop(eng, toks, 7, temperature, seed))


def test_generate_with_eos_reads_each_token(rng_key):
    """With ``eos_id`` the host reads every token as it is drawn (one sync
    each, beside the prefill's and the first decode step's) and serves the
    device loop's tokens up to the first EOS of each row."""
    cfg = registry.get_smoke("olmo-1b")
    eng = Engine(cfg, lm.init_params(cfg, rng_key), max_len=48)
    toks = np.random.RandomState(0).randint(3, 400, (3, 12)).astype(np.int32)
    free = eng.generate(toks, max_new=6)
    eos = int(free[0, 2])
    syncs = eng.obs.counter(N.ENGINE_HOST_SYNCS)
    s0 = syncs.value
    out = eng.generate(toks, max_new=6, eos_id=eos, pad_id=1)
    assert out.shape[1] > 1
    assert syncs.value - s0 == 2 + out.shape[1]
    for r in range(out.shape[0]):
        hits = np.where(free[r] == eos)[0]
        stop = hits[0] + 1 if hits.size else out.shape[1]
        np.testing.assert_array_equal(out[r, :stop], free[r, :stop])


@pytest.mark.parametrize("max_new,syncs", [(1, 2), (2, 3), (5, 3)])
def test_engine_host_syncs_land_in_the_given_registry(max_new, syncs, rng_key):
    """An engine given a registry counts its waits there, under its labels:
    a single-token call has no decode step to await."""
    cfg = registry.get_smoke("olmo-1b")
    reg = MetricsRegistry()
    eng = Engine(cfg, lm.init_params(cfg, rng_key), max_len=32, obs=reg,
                 obs_labels={"arch": cfg.name})
    toks = np.random.RandomState(1).randint(3, 400, (1, 6)).astype(np.int32)
    assert eng.generate(toks, max_new=max_new).shape == (1, max_new)
    assert eng.obs is reg
    assert reg.counter(N.ENGINE_HOST_SYNCS, arch=cfg.name).value == syncs


# -- continuous batching -------------------------------------------------------


def test_continuous_batching_completes_all():
    clock = {"t": 0.0}

    def fake_clock():
        clock["t"] += 0.01
        return clock["t"]

    sched = ContinuousBatcher(SchedulerConfig(max_batch=4, hedge_after_s=1e9),
                              clock=fake_clock)
    for i in range(20):
        sched.submit(Request(arrival=fake_clock(), id=f"r{i}", max_new=5))
    stats = sched.run_until_idle()
    assert stats["completed"] == 20
    assert stats["hedges"] == 0
    # slot reuse: 20 reqs x 5 steps / 4 slots = 25 min steps
    assert stats["steps"] >= 25


def test_straggler_hedging_triggers():
    clock = {"t": 0.0}

    def fake_clock():
        clock["t"] += 0.5  # slow steps -> deadline exceeded
        return clock["t"]

    sched = ContinuousBatcher(
        SchedulerConfig(max_batch=2, hedge_after_s=2.0, n_replicas=2),
        clock=fake_clock,
    )
    for i in range(4):
        sched.submit(Request(arrival=0.0, id=f"r{i}", max_new=30))
    stats = sched.run_until_idle()
    assert stats["completed"] == 4
    assert stats["hedges"] > 0
    assert stats["wasted_steps"] > 0  # hedging costs duplicated work


# -- tier pools ----------------------------------------------------------------


def test_tier_pool_round_robin_visits_every_replica():
    pool = TierPool("small", replicas=["r0", "r1", "r2"])
    # starts at replica 0 and cycles through all of them (the old
    # increment-before-index rotation never served slot 0)
    assert [pool.pick() for _ in range(6)] == ["r0", "r1", "r2", "r0", "r1", "r2"]


def test_tier_pool_hedged_dispatch_reuses_one_executor():
    pool = TierPool("large", replicas=["a", "b"])
    assert pool.dispatch(lambda e: e, hedge=True) in ("a", "b")
    ex = pool._executor
    assert ex is not None
    assert pool.dispatch(lambda e: e, hedge=True) in ("a", "b")
    assert pool._executor is ex  # one pool per TierPool, not per call
    pool.close()
    assert pool._executor is None


def test_tier_pool_hedged_failover_serves_surviving_replica():
    """A replica that times out must not surface: the hedge's success wins.
    With the failover guard ablated (the repro.sim seam) the single
    dispatch propagates the timeout."""
    def flaky(eng):
        if eng == "bad":
            raise TimeoutError("engine timed out")
        return f"served-by-{eng}"

    pool = TierPool("large", replicas=["bad", "good"])
    for _ in range(4):  # every rotation parity: failover always saves it
        assert pool.dispatch(flaky, hedge=True) == "served-by-good"
    pool.close()

    ablated = TierPool("large", replicas=["bad", "good"], hedge_failover=False)
    import pytest
    with pytest.raises(TimeoutError):
        ablated.dispatch(flaky, hedge=True)  # picks replica 0 ("bad")
    ablated.close()


def test_tier_pool_hedged_raises_only_when_all_replicas_fail():
    def always_bad(eng):
        raise RuntimeError(f"{eng} down")

    pool = TierPool("large", replicas=["a", "b"])
    import pytest
    with pytest.raises(RuntimeError):
        pool.dispatch(always_bad, hedge=True)
    pool.close()


def test_tier_pool_unhedged_skips_executor():
    pool = TierPool("actor", replicas=["only"])
    assert pool.dispatch(lambda e: e, hedge=True) == "only"  # <2 replicas
    assert pool._executor is None
    pool.close()


# -- two-tier router ------------------------------------------------------------


def test_router_routes_by_cache_and_async_cachegen():
    cache = PlanCache(capacity=10)
    calls = {"large": 0, "small": 0}

    router = TwoTierRouter(
        cache,
        extract_keyword=lambda req: req["kw"],
        plan_large=lambda req: calls.__setitem__("large", calls["large"] + 1)
        or {"plan": "fresh"},
        plan_small_with_template=lambda req, tpl: calls.__setitem__(
            "small", calls["small"] + 1
        )
        or {"plan": "adapted", "tpl": tpl},
        make_template=lambda req, res: {"tpl_for": req["kw"]},
        async_cachegen=True,
    )
    r1 = router.route({"kw": "mean calculation"})
    assert r1["plan"] == "fresh" and calls["large"] == 1
    router.drain()  # async insert lands
    r2 = router.route({"kw": "mean calculation"})
    assert r2["plan"] == "adapted" and calls["small"] == 1
    m = router.metrics.snapshot()
    assert m["hit_rate"] == 0.5 and m["async_cachegens"] == 1
    router.close()


def test_router_route_batch_single_lookup_pass():
    """route_batch answers the whole batch via one lookup_batch pass; fuzzy
    near-keywords resolve against the cache's incremental index."""
    cache = PlanCache(capacity=10, fuzzy=True, fuzzy_threshold=0.7)
    cache.insert("working capital ratio", {"tpl_for": "working capital ratio"})

    router = TwoTierRouter(
        cache,
        extract_keyword=lambda req: req["kw"],
        plan_large=lambda req: {"plan": "fresh"},
        plan_small_with_template=lambda req, tpl: {"plan": "adapted", "tpl": tpl},
        make_template=lambda req, res: {"tpl_for": req["kw"]},
        async_cachegen=False,
    )
    out = router.route_batch(
        [
            {"kw": "working capital ratio"},           # exact hit
            {"kw": "working capital ratio analysis"},  # fuzzy hit
            {"kw": "quantum chromodynamics"},          # miss -> large tier
        ]
    )
    assert [o["plan"] for o in out] == ["adapted", "adapted", "fresh"]
    m = router.metrics.snapshot()
    assert m["requests"] == 3
    assert m["small_tier_calls"] == 2 and m["large_tier_calls"] == 1
    # the miss distilled its template into the cache synchronously
    assert router.route({"kw": "quantum chromodynamics"})["plan"] == "adapted"
    router.close()


def test_router_async_does_not_block():
    # event-gated instead of sleep-timed: route() must RETURN while the
    # cache generation is still provably blocked on the event (no
    # wall-clock margins, so no flakiness on a loaded CI box)
    cache = PlanCache(capacity=10)
    release = threading.Event()
    slow = {"done": False}

    def make_template(req, res):
        assert release.wait(timeout=30)
        slow["done"] = True
        return {"t": 1}

    router = TwoTierRouter(
        cache,
        extract_keyword=lambda r: "k",
        plan_large=lambda r: "res",
        plan_small_with_template=lambda r, t: "hit",
        make_template=make_template,
        async_cachegen=True,
    )
    assert router.route({}) == "res"
    assert not slow["done"]  # response returned; cachegen still gated
    release.set()
    router.close()  # drains the pending cachegen
    assert slow["done"]
