"""CPU rehearsal of ``chip_smoke.py``: its phases at reduced sizes, its
device gate, and where the compile cache goes.

On the chip the same phases run at published widths (olmo-1b, qwen2.5-3b)
and a 65,536-row bank; here they catch wrong paths, arguments and checks
before a chip run is spent on them.
"""

import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.configs import registry
from repro.launch import compile_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_device_gate_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, env=env, timeout=240,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "not a TPU" in out.stderr


def test_device_gate_raises_in_process():
    with pytest.raises(SystemExit, match="not a TPU"):
        chip_smoke.device_gate()


def test_serve_phase_reduced():
    out = chip_smoke.phase_serve(full=False)
    assert out["tasks"] == chip_smoke.SERVE_TASKS
    assert out["hits"] >= 1 and out["misses"] >= 1
    assert set(out["tokens"]) == {"olmo-1b-smoke", "qwen2.5-3b-smoke"}
    assert out["index_topk_calls"] >= 1
    assert out["engine_calls"] > out["tasks"]


def test_numerics_phase_reduced():
    out = chip_smoke.phase_numerics(registry.get_smoke("olmo-1b"))
    assert 0.0 < out["rel_l2"] <= chip_smoke.REL_L2_BOUND


def test_index_phase_reduced():
    out = chip_smoke.phase_index(n_rows=4096)
    assert out["rows_equal"]
    # the check is meaningful only if ranks are far apart next to f32 error
    assert out["min_rank_gap"] > 100 * max(out["max_score_diff"], 1e-7)


def test_compile_cache_location(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # left to jax
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable() == str(compile_cache.REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(
            compile_cache.REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)
