"""Rehearsals of whole benchmark runs on the CPU, at smoke sizes.

Each drives ``bench.harness.run`` past its device gate through set-up, the
window and the checks, on cells cut from the real files
(``bench/tests/smoke.py``)."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench import harness, peaks  # noqa: E402
from bench.tests import smoke  # noqa: E402

HOT, COLD = "qwen2.5-3b.hot-templates", "qwen3-4b.cold-unique"
SEED = 2**33 + 99


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    """Metric readers look the device up in the peaks table; on the CPU
    the tests borrow the chip's entry (the numbers are not reported)."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))


def _run(loaded, *, trace=False, faults=(), seconds=0.6, control=False):
    return harness.run(loaded["cell"]["name"], SEED, seconds, trace, t_process=time.perf_counter(),
                       loaded=loaded, require_tpu=False, persistent_cache=False, faults=faults,
                       control=control)


@pytest.mark.parametrize("cell,hits", [(HOT, "all"), (COLD, "none")])
def test_sound_run_is_correct(cell, hits):
    r = _run(smoke.loaded(cell))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    assert r["window"]["compilations"] == 0
    assert set(r["metrics"]) >= {"task_s", "out_tok_per_s", "setup_s"}
    assert ("turn_p90_s" in r["metrics"]) == (cell == HOT)
    assert r["checks"]["replay_mismatch"]["value"] == 0
    hit_tasks = r["window"]["hits"]
    assert hit_tasks == (r["attempted"] if hits == "all" else 0)


def test_traced_run_reports_per_layer_metrics():
    r = _run(smoke.loaded(HOT), trace=True)
    assert r["correct"], r["checks"]
    for name in ("hit_rate", "lookup_ms", "prefill_ms", "decode_ms.big", "decode_ms.small", "mfu"):
        assert name in r["metrics"], name
    assert r["metrics"]["hit_rate"]["value"] == 100.0
    assert "task_s" not in r["metrics"]
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


def test_window_ends_at_a_task_boundary():
    class Agent:
        def __init__(self):
            self.cache = type("Cache", (), {"lookup": lambda self, kw: None,
                                            "__contains__": lambda self, kw: False})()
            self.started = 0

        def run_task(self, task):
            self.started += 1
            self.cache.lookup(task)
            time.sleep(0.05)
            return type("Rec", (), {"keyword": task, "answer": 1.0, "hit": False})()

    class Backend:
        recording = False

    agent = Agent()
    records, lookups, t0, t1, failed = harness.run_window(
        agent, Backend(), {}, [f"t{i}" for i in range(100)], 0.12)
    assert failed == 0
    assert len(records) == agent.started == len(lookups) == 3  # 3rd task in flight at 0.12 s
    assert t1 - t0 >= 0.12


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files run without an edit to any file that is there."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    cfg = json.loads((tmp_path / "bench/configs/apc-1chip-qwen3.json").read_text())
    cfg["name"] = "apc-1chip-alt"
    (tmp_path / "bench/configs/apc-1chip-alt.json").write_text(json.dumps(cfg))
    mix = json.loads((tmp_path / "bench/traffic/cold-unique.json").read_text())
    mix["intents"] = mix["intents"][:6]
    (tmp_path / "bench/traffic/cold-short.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/alt.cold-short.json").write_text(
        (tmp_path / "bench/limits/qwen3-4b.cold-unique.json").read_text())
    (tmp_path / "bench/metrics/tasks_done.py").write_text(
        "def read(run):\n    return float(len(run.records))\n")
    spec["configs"].append({"name": "apc-1chip-alt", "source": "https://example.org/alt",
                            "file": "bench/configs/apc-1chip-alt.json", "reduced": [],
                            "why": "test"})
    spec["workloads"].append({"name": "alt.cold-short", "config": "apc-1chip-alt",
                              "traffic": "cold-short", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "tasks_done", "unit": "tasks", "better": "higher",
                              "source": "program_counter", "layer": "agent loop",
                              "moves": "task_s", "workloads": ["alt.cold-short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    loaded = smoke.loaded("alt.cold-short", root=tmp_path)
    assert loaded["traffic"]["name"] == "cold-short"
    r = _run(loaded, trace=True, seconds=0.3)
    assert r["correct"], r["checks"]
    assert r["metrics"]["tasks_done"]["value"] == r["attempted"]


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", HOT, "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
