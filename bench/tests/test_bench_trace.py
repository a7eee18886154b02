"""The trace reduction on a small trace recorded on a TPU v5e
(``bench/tests/record_trace.py``: a prefill and four decode steps of two
toy jitted programs inside the harness's annotations, 3 ms host sleeps
before each decode step and after the role call)."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")


@pytest.fixture(scope="module")
def reduced():
    meta = json.load(open(os.path.join(DATA, "trace.json")))
    red = trace.reduce(os.path.join(DATA, "trace.xplane.pb"), window_s=meta["window_s"],
                       t_start=meta["t_start"], spans=meta["spans"])
    return meta, red


def test_one_device_busy_for_part_of_the_window(reduced):
    meta, red = reduced
    assert red.n_devices == 1
    assert 0 < red.busy_s < 0.01 * red.window_s  # toy programs of a few microseconds
    idle = sum(red.gap_seconds.values())
    assert idle + red.busy_s == pytest.approx(red.window_s, rel=1e-6)


def test_program_runs_are_named_and_tied_to_the_host(reduced):
    meta, red = reduced
    progs = [x.program for x in red.executions]
    assert progs == ["jit_prefill_fn"] + ["jit_decode_fn"] * meta["steps"]
    assert all(x.host_done is not None and x.dur > 0 for x in red.executions)
    (gen,) = red.spans("bench.generate")
    assert gen.attrs["arch"] == "toy" and int(gen.attrs["S"]) == 8
    steps = red.executions_in(gen, "jit_decode_fn")
    assert len(steps) == meta["steps"]
    assert [x.start for x in steps] == sorted(x.start for x in steps)


def test_host_spans_share_the_profilers_clock(reduced):
    meta, red = reduced
    (act,) = red.spans("bench.act")
    (gen,) = red.spans("bench.generate")
    (eng,) = red.spans("engine.generate")  # the program's span, from its host clock
    assert act.start <= gen.start <= eng.start and eng.end <= gen.end <= act.end
    # the program span lands within a millisecond of where the annotation says
    assert abs((eng.start - gen.start) - (meta["spans"][0]["start"] - meta["t_start"]) * 1e9) < 1e6


def test_idle_gaps_are_put_down_to_the_host_span_around_them(reduced):
    meta, red = reduced
    inside = red.gap_seconds["engine.generate"]
    assert inside >= meta["steps"] * meta["sleep_s"]
    assert red.gap_seconds[trace.OUTSIDE] >= 0.5 * meta["sleep_s"]
    b = red.breakdown()
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "engine.generate"
    assert all(name.split("/")[0] in ("jit_prefill_fn", "jit_decode_fn")
               for name, _ in b["device_ops"])


def test_union_merges_overlaps():
    assert trace._union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
