"""repro.obs: metrics registry, structured spans, exporters, attribution.

Covers the four contracts the observability spine makes:

* histogram percentiles track a numpy reference within bucket resolution;
* a traced ``route_batch`` produces the documented span tree
  (router -> distributed lookup -> per-tier -> shard -> pipeline stage)
  with attribute propagation and tokens_saved attribution on hits;
* every counter write is thread-safe — concurrent route_batch + async
  cachegen must conserve requests = hits + misses exactly (the seed had a
  data race here: cachegen-pool threads bumped RouterMetrics unlocked);
* the sim emits byte-identical span streams for identical seeds;
* a served task is one span tree down to each prefill, decode step and
  sample, the compile counter counts each compile once, and a host-clock
  tracer's spans appear natively on the profiler's host plane.
"""

import json
import threading

import numpy as np
import pytest

from repro.core.distributed_cache import DistributedPlanCache
from repro.obs import (
    Histogram,
    InMemoryExporter,
    JsonlExporter,
    MetricsRegistry,
    NOOP_SPAN,
    Tracer,
    chrome_trace,
    current_span,
    get_tracer,
    latency_buckets,
    pow2_buckets,
    trace_span,
    use_tracer,
)
from repro.obs import names as N
from repro.serving.router import TwoTierRouter
from repro.sim import SimConfig, run_sim

# -- registry ------------------------------------------------------------------


def test_registry_counter_gauge_labels():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2)
    reg.counter("c", shard="cache-1").inc(5)
    g = reg.gauge("g")
    g.set(7)
    g.dec(3)
    snap = reg.snapshot()
    assert snap["c"][""] == 3
    assert snap["c"]["shard=cache-1"] == 5
    assert snap["g"][""] == 4
    # same (name, labels) -> same instance, regardless of kwarg order
    h1 = reg.histogram("h", a="1", b="2")
    h2 = reg.histogram("h", b="2", a="1")
    assert h1 is h2


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")
    with pytest.raises(ValueError):
        reg.histogram("x")


def test_registry_snapshot_is_canonical_and_resettable():
    reg = MetricsRegistry()
    reg.counter("b").inc()
    reg.counter("a", z="1").inc(2)
    reg.histogram("lat").observe(0.5)
    s1 = json.dumps(reg.snapshot(), sort_keys=True)
    s2 = json.dumps(reg.snapshot(), sort_keys=True)
    assert s1 == s2
    reg.reset()
    assert reg.snapshot()["a"]["z=1"] == 0
    assert reg.snapshot()["lat"][""]["count"] == 0


# -- histogram percentile math -------------------------------------------------


def test_histogram_percentiles_track_numpy():
    rs = np.random.RandomState(7)
    samples = rs.lognormal(mean=-5.0, sigma=1.2, size=4000)
    h = Histogram("lat", bounds=latency_buckets())
    for s in samples:
        h.observe(float(s))
    for q in (50.0, 90.0, 99.0):
        ref = float(np.percentile(samples, q))
        est = h.percentile(q)
        # geometric x2 buckets: the interpolated estimate must land within
        # one bucket (a factor of 2) of the numpy reference...
        assert ref / 2 <= est <= ref * 2, (q, ref, est)
        # ...and inside the observed range
        assert samples.min() <= est <= samples.max()
    # monotone in q
    qs = [h.percentile(q) for q in (10, 50, 90, 99, 100)]
    assert qs == sorted(qs)
    snap = h.snapshot()
    assert snap["count"] == len(samples)
    assert snap["mean"] == pytest.approx(samples.mean(), rel=1e-6)
    assert snap["max"] == pytest.approx(samples.max())


def test_histogram_degenerate_and_empty():
    h = Histogram("x")
    assert h.percentile(50) is None
    for _ in range(10):
        h.observe(0.37)
    # all mass in one bucket: clamping to observed min/max makes every
    # percentile exact
    assert h.percentile(50) == pytest.approx(0.37)
    assert h.percentile(99) == pytest.approx(0.37)


def test_pow2_buckets_bucket_small_counts_exactly():
    h = Histogram("cand", bounds=pow2_buckets(8))
    for v in (0, 1, 2, 3, 4, 1000):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 6
    assert snap["max"] == 1000
    assert snap["buckets"]["le_1"] == 2  # 0 and 1
    assert snap["buckets"]["le_2"] == 1
    assert snap["buckets"]["le_4"] == 2  # 3 and 4


# -- spans: nesting, attributes, exporters -------------------------------------


def test_span_nesting_and_attribute_propagation():
    mem = InMemoryExporter()
    fake = {"t": 0.0}

    def clock():
        fake["t"] += 0.25
        return fake["t"]

    tracer = Tracer(clock=clock, exporters=[mem])
    with use_tracer(tracer):
        with trace_span("outer", a=1) as outer:
            assert current_span() is outer
            with trace_span("inner", b=2) as inner:
                assert current_span() is inner
                inner.event("cache.attribution", i=0, hit=False)
            assert current_span() is outer
        assert current_span() is NOOP_SPAN
    # children export before parents (exported on end)
    assert [s["name"] for s in mem.spans] == ["inner", "outer"]
    inner_d, outer_d = mem.spans
    assert inner_d["parent_id"] == outer_d["span_id"]
    assert outer_d["parent_id"] is None
    assert outer_d["attrs"] == {"a": 1}
    assert inner_d["attrs"] == {"b": 2}
    assert inner_d["events"][0]["name"] == "cache.attribution"
    assert outer_d["start"] < inner_d["start"] <= inner_d["end"] <= outer_d["end"]


def test_tracer_disabled_is_noop():
    assert get_tracer().n_spans == 0  # NoopTracer outside use_tracer
    with trace_span("anything", x=1) as sp:
        assert sp is NOOP_SPAN
        sp.set(y=2)
        sp.event("e")  # all swallowed


def test_jsonl_lines_are_canonical_and_chrome_trace_loads(tmp_path):
    mem = InMemoryExporter()
    path = tmp_path / "t.jsonl"
    jsonl = JsonlExporter(str(path))
    tracer = Tracer(exporters=[mem, jsonl])
    with use_tracer(tracer):
        with trace_span("outer"):
            with trace_span("inner", k="v"):
                pass
    jsonl.close()
    lines = path.read_text().splitlines()
    assert lines == mem.lines()
    for line in lines:
        assert json.dumps(json.loads(line), sort_keys=True,
                          separators=(",", ":")) == line
    doc = chrome_trace(mem.spans)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert sorted(e["name"] for e in xs) == ["inner", "outer"]
    # one admission tree = one track: tid is the root span id
    root = [s for s in mem.spans if s["parent_id"] is None][0]["span_id"]
    assert {e["tid"] for e in xs} == {root}


# -- the traced serving path ---------------------------------------------------


def _router(cache, **kw):
    return TwoTierRouter(
        cache,
        extract_keyword=lambda r: r["kw"],
        plan_large=lambda r: {"plan": f"fresh:{r['kw']}"},
        plan_small_with_template=lambda r, tpl: {"plan": "adapted", "tpl": tpl},
        make_template=lambda r, res: res["plan"],
        **kw,
    )


def _trace_route_batch(async_cachegen=False):
    mem = InMemoryExporter()
    tracer = Tracer(exporters=[mem])
    cache = DistributedPlanCache(2, fuzzy=True, fuzzy_threshold=0.5,
                                 capacity_per_node=32)
    router = _router(cache, async_cachegen=async_cachegen)
    with use_tracer(tracer):
        router.route_batch([{"kw": "alpha beta"}, {"kw": "gamma delta"}])
        out = router.route_batch(
            [{"kw": "alpha beta"},          # exact hit
             {"kw": "alpha beta please"},   # fuzzy hit
             {"kw": "zeta eta"}])           # miss
        router.drain()
    router.close()
    return mem, router, out


def test_route_batch_span_tree_and_attribution():
    mem, router, out = _trace_route_batch()
    by_id = {s["span_id"]: s for s in mem.spans}

    def ancestry(s):
        names = []
        pid = s["parent_id"]
        while pid is not None:
            names.append(by_id[pid]["name"])
            pid = by_id[pid]["parent_id"]
        return names

    # the acceptance chain: a match.stage span whose ancestry walks up
    # through the shard cache, the tier fan-out, the distributed lookup,
    # and the router batch
    chains = [
        ancestry(s) for s in mem.spans if s["name"] == N.SPAN_MATCH_STAGE
    ]
    assert any(
        set(c) >= {N.SPAN_CACHE_LOOKUP, N.SPAN_SHARD_CALL, N.SPAN_DCACHE_TIER,
                   N.SPAN_DCACHE_LOOKUP, N.SPAN_ROUTER_LOOKUP,
                   N.SPAN_ROUTE_BATCH}
        for c in chains
    ), chains
    # attribute propagation: shard label on the per-shard cache span,
    # stage name on the pipeline stage span, backend on index.topk
    cache_spans = [s for s in mem.spans if s["name"] == N.SPAN_CACHE_LOOKUP]
    assert {s["attrs"]["shard"] for s in cache_spans} <= {"cache-0", "cache-1"}
    stages = {s["attrs"]["stage"] for s in mem.spans
              if s["name"] == N.SPAN_MATCH_STAGE}
    assert "exact" in stages and "fuzzy" in stages
    topk = [s for s in mem.spans if s["name"] == N.SPAN_INDEX_TOPK]
    assert topk and all("backend" in s["attrs"] for s in topk)

    # attribution: batch 2 had 2 hits, 1 miss
    batches = [s for s in mem.spans if s["name"] == N.SPAN_ROUTE_BATCH]
    events = [ev for s in batches for ev in s["events"]
              if ev["name"] == N.EVENT_ATTRIBUTION]
    assert len(events) == 5  # one per routed request
    hits = [ev["attrs"] for ev in events if ev["attrs"]["hit"]]
    misses = [ev["attrs"] for ev in events if not ev["attrs"]["hit"]]
    assert len(hits) == 2 and len(misses) == 3
    for a in hits:
        assert a["tier"] == "small"
        assert a["tokens_saved"] >= 1
        assert a["stage"] in ("exact", "fuzzy")
        assert a["node"] in ("cache-0", "cache-1")
        assert "matched_key" in a and "replica_tier" in a
    assert {a["stage"] for a in hits} == {"exact", "fuzzy"}
    assert all(a["tier"] == "large" for a in misses)
    assert router.metrics.tokens_saved == sum(a["tokens_saved"] for a in hits)


def test_async_cachegen_spans_parent_to_submitting_route():
    mem, router, _ = _trace_route_batch(async_cachegen=True)
    gens = [s for s in mem.spans if s["name"] == N.SPAN_CACHEGEN]
    assert gens, "async cachegen produced no spans"
    by_id = {s["span_id"]: s for s in mem.spans}
    for g in gens:
        assert by_id[g["parent_id"]]["name"] in (N.SPAN_ROUTE,
                                                 N.SPAN_ROUTE_BATCH)
    fates = [ev["attrs"]["fate"] for s in mem.spans for ev in s["events"]
             if ev["name"] == N.EVENT_CACHEGEN_FATE]
    assert fates and set(fates) <= {"async", "sync_fallback", "dropped"}


def test_instrumented_names_stay_inside_catalog():
    mem, router, _ = _trace_route_batch(async_cachegen=True)
    span_names = {s["name"] for s in mem.spans}
    assert span_names <= set(N.SPAN_NAMES), span_names - set(N.SPAN_NAMES)
    event_names = {ev["name"] for s in mem.spans for ev in s["events"]}
    assert event_names <= set(N.EVENT_NAMES)
    # the shared registry saw only catalogued metric names
    reg_names = set(router.metrics.registry.names())
    assert reg_names <= set(N.METRIC_NAMES), reg_names - set(N.METRIC_NAMES)


def test_one_registry_spans_router_store_and_index():
    reg = MetricsRegistry()
    cache = DistributedPlanCache(2, fuzzy=True, capacity_per_node=32, obs=reg)
    router = _router(cache)  # auto-discovers cache.obs
    router.route_batch([{"kw": "alpha beta"}])  # miss -> sync admission
    router.route_batch([{"kw": "alpha beta"}])  # exact hit
    router.close()
    snap = reg.snapshot()
    assert snap[N.ROUTER_REQUESTS][""] == 2
    assert snap[N.ROUTER_HITS][""] == 1
    # per-shard store series carry the shard label
    assert set(snap[N.CACHE_HITS]) >= {"", "shard=cache-0", "shard=cache-1"}
    facade_hits = snap[N.CACHE_HITS][""]
    shard_hits = sum(v for k, v in snap[N.CACHE_HITS].items() if k)
    assert facade_hits == shard_hits == 1
    assert snap[N.ROUTER_LOOKUP_LATENCY][""]["count"] == 2


# -- thread safety (the seed's RouterMetrics data race) ------------------------


def test_concurrent_route_batch_with_async_cachegen_conserves_counts():
    mem = InMemoryExporter()
    tracer = Tracer(exporters=[mem])
    cache = DistributedPlanCache(2, fuzzy=False, capacity_per_node=4096)
    router = _router(cache, async_cachegen=True)
    n_threads, per_thread = 8, 30
    barrier = threading.Barrier(n_threads)
    errors = []

    def worker(t):
        try:
            barrier.wait()
            for i in range(per_thread):
                # ~half repeats (hits after first admission), ~half unique
                kw = f"shared-{i % 5}" if i % 2 else f"uniq-{t}-{i}"
                router.route_batch([{"kw": kw}])
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    with use_tracer(tracer):
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        router.drain()
    router.close()
    assert not errors
    m = router.metrics
    total = n_threads * per_thread
    assert m.requests == total
    assert m.hits + m.misses == total
    # the raced counters: every miss wave is accounted to exactly one fate
    assert (m.async_cachegens + m.sync_cachegen_fallbacks
            + m.cachegen_dropped) == m.misses
    assert m.lookup_latency.snapshot()["count"] == total
    # span ids unique even under contention
    ids = [s["span_id"] for s in mem.spans]
    assert len(ids) == len(set(ids))


def test_registry_counter_parallel_increments_exact():
    reg = MetricsRegistry()
    c = reg.counter("n")

    def bump():
        for _ in range(10_000):
            c.inc()

    threads = [threading.Thread(target=bump) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 80_000


# -- back-compat views ---------------------------------------------------------


def test_snapshot_schemas_preserved_for_migrated_islands():
    cache = DistributedPlanCache(2, fuzzy=True, capacity_per_node=32)
    router = _router(cache)
    router.route_batch([{"kw": "a b"}, {"kw": "a b"}])
    router.close()
    m = router.metrics.snapshot()
    for k in ("requests", "hit_rate", "large_tier_calls", "small_tier_calls",
              "async_cachegens", "sync_cachegen_fallbacks",
              "cachegen_dropped", "lookup_s", "tokens_saved",
              "lookup_latency"):
        assert k in m
    s = cache.stats.snapshot()
    assert set(s) >= {"hits", "misses", "inserts", "evictions", "hit_rate"}
    # reset-on-clear: the shared-registry views must zero, not detach
    cache.clear()
    assert cache.stats.hits == 0 and cache.stats.inserts == 0


# -- sim determinism -----------------------------------------------------------


@pytest.mark.parametrize("fault", ["none", "async_cachegen"])
def test_sim_span_stream_is_byte_identical_per_seed(fault):
    cfg = SimConfig(seed=11, scenario="skewed_reuse", fault=fault, n_ops=20)
    a = run_sim(cfg)
    b = run_sim(cfg)
    assert a.n_spans > 0
    assert a.span_digest == b.span_digest
    assert a.trace_hash == b.trace_hash
    assert a.span_summary == b.span_summary
    assert N.SPAN_DCACHE_LOOKUP in a.span_summary
    other = run_sim(SimConfig(seed=12, scenario="skewed_reuse", fault=fault,
                              n_ops=20))
    assert other.span_digest != a.span_digest


# -- serving-path spans and the compile counter ---------------------------------


def _smoke_engine(rng_key, max_len=48):
    from repro.configs import registry
    from repro.models import lm
    from repro.serving.engine import Engine

    cfg = registry.get_smoke("olmo-1b")
    return Engine(cfg, lm.init_params(cfg, rng_key), max_len=max_len)


def test_engine_generate_spans_prefill_each_decode_step_and_sample(rng_key):
    eng = _smoke_engine(rng_key)
    toks = np.random.RandomState(0).randint(3, 400, (2, 10)).astype(np.int32)
    eng.generate(toks, max_new=2)  # compiles outside the traced call
    mem = InMemoryExporter()
    d0 = eng.stats.decode_s
    max_new = 5
    with use_tracer(Tracer(exporters=[mem])):
        out = eng.generate(toks, max_new=max_new)
    assert out.shape == (2, max_new)
    by_name = {}
    for s in mem.spans:
        by_name.setdefault(s["name"], []).append(s)
    (gen,) = by_name[N.SPAN_ENGINE_GENERATE]
    assert gen["attrs"]["arch"] == eng.cfg.name
    assert len(by_name[N.SPAN_ENGINE_PREFILL]) == 1
    assert by_name[N.SPAN_ENGINE_PREFILL][0]["attrs"] == {"S": 10, "path": "full"}
    assert len(by_name[N.SPAN_ENGINE_DECODE]) == max_new - 1
    assert len(by_name[N.SPAN_ENGINE_SAMPLE]) == max_new
    children = [s for s in mem.spans if s is not gen]
    assert all(s["parent_id"] == gen["span_id"] for s in children), children
    # per-step spans carry nothing but their timing
    assert all(s["attrs"] == {} for s in by_name[N.SPAN_ENGINE_DECODE]
               + by_name[N.SPAN_ENGINE_SAMPLE])
    # the decode spans cover the steps' dispatch, inside the decode phase
    # EngineStats times: from the first step's dispatch (within the first
    # span) to the tokens on the host (before the call's span closes)
    decodes = by_name[N.SPAN_ENGINE_DECODE]
    spans_s = sum(s["end"] - s["start"] for s in decodes)
    stats_s = eng.stats.decode_s - d0
    assert spans_s <= stats_s
    assert decodes[-1]["end"] - decodes[0]["end"] <= stats_s
    assert stats_s <= gen["end"] - decodes[0]["start"]
    # steps follow each other: prefill, then sample and decode in turn
    order = [s["name"] for s in sorted(children, key=lambda s: s["start"])]
    assert order == ([N.SPAN_ENGINE_PREFILL]
                     + [N.SPAN_ENGINE_SAMPLE, N.SPAN_ENGINE_DECODE] * (max_new - 1)
                     + [N.SPAN_ENGINE_SAMPLE])


def test_noop_tracer_records_nothing_and_allocates_no_span(rng_key, monkeypatch):
    from repro.obs import spans as spans_mod

    eng = _smoke_engine(rng_key)
    toks = np.random.RandomState(0).randint(3, 400, (1, 8)).astype(np.int32)

    class NoSpan:
        def __init__(self, *a, **kw):
            raise AssertionError("a span was allocated with tracing off")

    monkeypatch.setattr(spans_mod, "Span", NoSpan)
    assert get_tracer().n_spans == 0
    out = eng.generate(toks, max_new=4)
    assert out.shape == (1, 4)
    assert get_tracer().n_spans == 0


def test_one_task_is_one_span_tree(rng_key):
    from repro.configs.apc_minion import DEFAULT
    from repro.core.agent_loop import AgentConfig, PlanActAgent
    from repro.core.cost_model import CostLedger
    from repro.envs.workloads import get_env
    from repro.serving.jax_backend import JaxBackend

    eng = _smoke_engine(rng_key, max_len=96)
    engines = {r: eng for r in
               ("large_planner", "small_planner", "actor", "keyword_extractor")}
    agent = PlanActAgent(JaxBackend(engines, seed=0, max_exec_tokens=3),
                         CostLedger(pricing_map=dict(DEFAULT.pricing)),
                         AgentConfig(method="apc"))
    (task,) = get_env("tabmwp").generate(1, seed=0)
    mem = InMemoryExporter()
    with use_tracer(Tracer(exporters=[mem])):
        rec = agent.run_task(task)
    by_id = {s["span_id"]: s for s in mem.spans}
    (root,) = [s for s in mem.spans if s["parent_id"] is None]
    assert root["name"] == N.SPAN_AGENT_TASK
    assert root["attrs"] == {"task": task.id, "method": "apc", "hit": rec.hit}

    def chain(s):
        names = [s["name"]]
        while s["parent_id"] is not None:
            s = by_id[s["parent_id"]]
            names.append(s["name"])
        return names[::-1]

    calls = [s for s in mem.spans if s["name"] == N.SPAN_BACKEND_CALL]
    assert calls and all(s["parent_id"] == root["span_id"] for s in calls)
    assert {s["attrs"]["role"] for s in calls} >= {"keyword_extractor", "actor"}
    for s in mem.spans:
        if s["name"] in (N.SPAN_ENGINE_PREFILL, N.SPAN_ENGINE_DECODE,
                         N.SPAN_ENGINE_SAMPLE):
            assert chain(s)[:3] == [N.SPAN_AGENT_TASK, N.SPAN_BACKEND_CALL,
                                    N.SPAN_ENGINE_GENERATE], chain(s)
        if s["name"] == N.SPAN_CACHE_LOOKUP:
            assert chain(s)[0] == N.SPAN_AGENT_TASK
    assert {s["name"] for s in mem.spans} <= set(N.SPAN_NAMES)


def test_compile_counter_counts_a_fresh_shape_once(rng_key):
    import jax

    from repro.core.agent_loop import AgentConfig, PlanActAgent
    from repro.core.backends import SimulatedBackend
    from repro.core.cost_model import CostLedger
    from repro.configs.apc_minion import DEFAULT
    from repro.envs.workloads import get_env
    from repro.obs import compile_counter

    counter = compile_counter()
    # one counter and one listener: a second listener would count each
    # compile below twice
    assert compile_counter() is counter

    def triple(x):
        return x * 3 + 1

    f = jax.jit(triple)
    n0 = counter.total
    series = counter.registry.counter(N.JIT_COMPILES, program="jit(triple)")
    mem = InMemoryExporter()
    with use_tracer(Tracer(exporters=[mem])):
        with trace_span("outer") as outer:
            f(np.ones(5, np.float32))
    assert counter.total - n0 == 1 and series.value == 1
    (comp,) = [s for s in mem.spans if s["name"] == N.SPAN_JIT_COMPILE]
    assert comp["attrs"] == {"program": "jit(triple)"}
    assert comp["parent_id"] == outer.span_id
    assert outer.start <= comp["start"] <= comp["end"] <= outer.end_time
    seconds = counter.registry.counter(N.JIT_COMPILE_S, program="jit(triple)").value
    assert seconds == pytest.approx(comp["end"] - comp["start"], abs=1e-3)
    f(np.ones(5, np.float32))  # a repeat compiles nothing
    assert counter.total - n0 == 1 and series.value == 1
    f(np.ones(6, np.float32))  # a new shape compiles once more
    assert counter.total - n0 == 2 and series.value == 2

    class CompilingBackend(SimulatedBackend):
        """Extracting a keyword runs ``triple`` at the query's length."""

        def extract_keyword(self, task):
            f(np.ones(len(task.query), np.float32))
            return super().extract_keyword(task)

    cfg = AgentConfig(method="apc", index_backend="brute")
    agent = PlanActAgent(CompilingBackend(seed=0),
                         CostLedger(pricing_map=dict(DEFAULT.pricing)), cfg)
    (task,) = get_env("tabmwp").generate(1, seed=3)
    assert len(task.query) not in (5, 6)
    n1 = counter.total
    first = agent.run_task(task)
    assert first.compiles == counter.total - n1 >= 1
    assert agent.run_task(task).compiles == 0


def test_compile_span_needs_the_host_clock():
    import jax

    from repro.obs import compile_counter

    counter = compile_counter()
    mem = InMemoryExporter()
    fake = {"t": 0.0}

    def clock():
        fake["t"] += 1.0
        return fake["t"]

    f = jax.jit(lambda x: x - 7)
    n0 = counter.total
    with use_tracer(Tracer(clock=clock, exporters=[mem])):
        with trace_span("outer"):
            f(np.ones(3, np.float32))
    assert counter.total - n0 == 1
    assert [s["name"] for s in mem.spans] == ["outer"]


def test_program_spans_sit_on_the_profilers_host_plane(rng_key, tmp_path):
    """Under ``jax.profiler`` each span of a host-clock tracer is a host
    event of the same name, nested as the spans are; a tracer on another
    clock leaves no event."""
    import glob

    import jax
    from jax.profiler import ProfileData

    eng = _smoke_engine(rng_key)
    toks = np.random.RandomState(0).randint(3, 400, (1, 8)).astype(np.int32)
    eng.generate(toks, max_new=3)
    mem = InMemoryExporter()
    fake = {"t": 0.0}

    def clock():
        fake["t"] += 1.0
        return fake["t"]

    jax.profiler.start_trace(str(tmp_path))
    try:
        with use_tracer(Tracer(exporters=[mem])):
            with trace_span(N.SPAN_AGENT_TASK, task="t0"):
                eng.generate(toks, max_new=3)
        with use_tracer(Tracer(clock=clock)):
            with trace_span(N.SPAN_BACKEND_CALL, role="virtual"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {s["name"] for s in mem.spans}
    native = [e for plane in ProfileData.from_file(path).planes
              if plane.name == "/host:CPU" for line in plane.lines
              for e in line.events if e.name in names | {N.SPAN_BACKEND_CALL}]
    assert sorted(e.name for e in native) == sorted(s["name"] for s in mem.spans)

    def within(inner, outer):
        return (outer.start_ns <= inner.start_ns
                and inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns)

    (task,) = [e for e in native if e.name == N.SPAN_AGENT_TASK]
    (gen,) = [e for e in native if e.name == N.SPAN_ENGINE_GENERATE]
    assert dict(task.stats) == {"task": "t0"}
    assert dict(gen.stats)["arch"] == eng.cfg.name
    assert within(gen, task)
    steps = [e for e in native if e.name in (N.SPAN_ENGINE_PREFILL, N.SPAN_ENGINE_DECODE,
                                             N.SPAN_ENGINE_SAMPLE)]
    assert len(steps) == 1 + 2 + 3
    assert all(within(e, gen) for e in steps)
