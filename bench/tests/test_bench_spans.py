"""The readers of the program's own spans and compile counter
(``sample_ms.big``, ``sample_ms.small``, ``sample_idle_share``,
``compiles``) on synthetic runs, with the instrumentation and without it
as the program had it before, and ``bench.spancheck`` on the CPU."""

from __future__ import annotations

import os
import sys
import time
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness, peaks, spancheck  # noqa: E402
from bench.tests import smoke  # noqa: E402
from bench.trace import OUTSIDE, HostSpan, Reduction  # noqa: E402

HOT = "qwen2.5-3b.hot-templates"
SEED = 2**33 + 77
CONFIG = {"roles": {"large_planner": "big-lm", "actor": "big-lm", "small_planner": "small-lm",
                    "keyword_extractor": "small-lm"},
          "models": {"big-lm": {}, "small-lm": {}}}
MS = 1e6  # ns


def _call(arch, start, steps, sample_ms, decode_ms, *, spans=True):
    """A traced generate call: the harness's annotation and, where the
    program records them, its prefill, sample and decode spans."""
    host = []
    t = start + MS  # 1 ms prefill
    if spans:
        host.append(HostSpan("engine.prefill", start, t, {}))
    for i in range(steps):
        if spans:
            host.append(HostSpan("engine.sample", t, t + sample_ms * MS, {}))
        t += sample_ms * MS
        if i + 1 < steps:
            if spans:
                host.append(HostSpan("engine.decode", t, t + decode_ms * MS, {}))
            t += decode_ms * MS
    gen = [HostSpan("bench.generate", start, t + 0.01 * MS, {"arch": arch, "S": 8})]
    if spans:
        gen.append(HostSpan("engine.generate", start, t, {"arch": arch}))
    return gen + host, t


def _trace(spans=True, sampling=True, n_devices=1):
    host, t = [], 0.0
    for arch, sample_ms in (("big-lm", 4.0), ("small-lm", 3.0), ("big-lm", 2.0)):
        call, t = _call(arch, t + 2 * MS, 3, sample_ms, 10.0, spans=spans)
        host += call
    if not sampling:
        host = [h for h in host if h.name != "engine.sample"]
    gaps = {"engine.generate": 0.02, OUTSIDE: 0.005}
    if spans and sampling:
        gaps = {"engine.sample": 0.015, "engine.generate": 0.001, OUTSIDE: 0.005}
    return Reduction(window_s=t / 1e9, busy_s=0.03, n_devices=n_devices, executions=[],
                     host=host, op_seconds={}, gap_seconds=gaps)


def _run(trace=None, records=()):
    return harness.RunData(config=CONFIG, traffic={}, device_kind="TPU v5 lite", setup_s=1.0,
                           window_s=1.0, records=list(records), turns=[], calls=[], engines={},
                           trace=trace)


def _read(name, run):
    return harness.reader(name)(run)


def test_sample_ms_is_the_mean_sample_span_of_each_engine():
    run = _run(_trace())
    assert _read("sample_ms.big", run) == pytest.approx((3 * 4.0 + 3 * 2.0) / 6)
    assert _read("sample_ms.small", run) == pytest.approx(3.0)


def test_sample_idle_share_reads_the_idle_put_down_to_sampling():
    t = _trace()
    assert _read("sample_idle_share", _run(t)) == pytest.approx(100 * 0.015 / t.window_s)


@pytest.mark.parametrize("name", ["sample_ms.big", "sample_ms.small", "sample_idle_share"])
def test_span_readers_give_nothing_without_the_programs_spans(name):
    assert _read(name, _run(None)) is None  # an untraced run
    assert _read(name, _run(_trace(spans=False))) is None  # a program without engine spans


@pytest.mark.parametrize("name", ["sample_ms.big", "sample_ms.small", "sample_idle_share"])
def test_span_readers_read_zero_once_sampling_leaves_the_host(name):
    assert _read(name, _run(_trace(sampling=False))) == 0


def test_sample_idle_share_needs_a_device_trace():
    assert _read("sample_idle_share", _run(_trace(n_devices=0))) is None


def test_compiles_sums_the_records_and_needs_the_counter():
    def rec(**kw):
        return SimpleNamespace(hit=True, **kw)

    assert _read("compiles", _run(records=[rec(compiles=0), rec(compiles=2),
                                           rec(compiles=1)])) == 3
    assert _read("compiles", _run(records=[rec(compiles=0)] * 4)) == 0
    assert _read("compiles", _run(records=[rec(), rec()])) is None  # no RunRecord.compiles
    assert _read("compiles", _run(records=[])) is None


@pytest.fixture
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def _spancheck(trace, tracer):
    return spancheck.run(smoke.loaded(HOT), SEED, 0.6, trace, tracer,
                         t_process=time.perf_counter(), require_tpu=False,
                         persistent_cache=False)


def test_traced_run_places_each_span_by_its_own_host_event(cpu_peaks):
    r = _spancheck(True, False)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["sample_ms.big"]["value"] > 0 and m["sample_ms.small"]["value"] > 0
    assert m["compiles"]["value"] == r["window"]["compilations"]
    assert "sample_idle_share" not in m  # the CPU has no device plane
    c = r["clock"]
    counts = c["by_name"]
    for name in ("agent.task", "backend.call", "engine.generate", "engine.prefill",
                 "engine.decode", "engine.sample"):
        assert counts[name][0] == counts[name][1] > 0, (name, counts)
    assert "jit.compile" not in counts
    assert c["paired"] == sum(n for n, _ in counts.values())
    assert c["median_us"] < 1000


@pytest.mark.parametrize("tracer", [False, True])
def test_untraced_window_with_and_without_a_tracer(cpu_peaks, tracer):
    r = _spancheck(False, tracer)
    assert r["correct"], r["checks"]
    assert "task_s" in r["metrics"]
    t = r["tracing"]
    assert t["tracer"] is tracer and t["decode_ms.big"] > 0
    assert t["compiles"] == r["window"]["compilations"]
    assert (t["spans"] > 0) is tracer
