"""Faults planted on the timed path turn ``correct`` false, and so does the
fp8 control put in the engines' place (CPU, smoke sizes)."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench.tests import smoke  # noqa: E402
from bench.tests.test_bench_run import COLD, HOT, SEED, _run, cpu_peaks  # noqa: E402,F401


def _alter_token(monkeypatch):
    """A served token altered where it is produced: every third token the
    engines' sampler draws is replaced by its successor."""
    import repro.serving.engine as engine_mod

    inner, n = engine_mod.sample_token, [0]

    def sample_token(logits, *a, **kw):
        tok = inner(logits, *a, **kw)
        n[0] += 1
        return (tok + 1) % 50304 if n[0] % 3 == 0 else tok

    def fault(by_arch, agent):
        monkeypatch.setattr(engine_mod, "sample_token", sample_token)

    return fault


def _stale_decode(monkeypatch):
    return _stale_decode_fault


def _stale_decode_fault(by_arch, agent):
    """A decode step that returns its state unchanged."""
    for eng in by_arch.values():
        inner = eng._decode

        def decode(params, cache, tokens, _inner=inner):
            logits, _ = _inner(params, dict(cache), tokens)
            return logits, cache

        eng._decode = decode


def _wrong_template(monkeypatch):
    return _wrong_template_fault


def _wrong_template_fault(by_arch, agent):
    """An answer altered where it is produced: the plan cache answers the
    second lookup of the window with a miss."""
    inner = agent.cache.lookup_batch
    count = {"n": 0}

    def lookup_batch(keywords, **kw):
        out = inner(keywords, **kw)
        count["n"] += 1
        return [None] * len(out) if count["n"] == 2 else out

    agent.cache.lookup_batch = lookup_batch


@pytest.mark.parametrize("fault", [_alter_token, _stale_decode, _wrong_template],
                         ids=["token-altered", "state-unchanged", "cache-answer-altered"])
def test_a_fault_on_the_timed_path_turns_correct_false(fault, monkeypatch):
    r = _run(smoke.loaded(HOT), faults=[fault(monkeypatch)])
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("cell", [HOT, COLD])
def test_control_in_the_engines_place_is_not_correct(cell):
    """A whole run whose check compares the tokens the fp8 control puts
    first, under the cell's committed limits, comes out not correct. At
    smoke widths the logits are of unit scale only with larger weights."""
    r = _run(smoke.loaded(cell, weight_std=0.4), control=True)
    assert not r["correct"], r["checks"]
    gaps = {k: v for k, v in r["checks"].items() if k.startswith("gap.")}
    assert gaps
    for name, g in gaps.items():
        assert g["value"] > g["limit"] > g["served"], (name, g)


def test_control_reads_far_above_the_served_tokens():
    """The fp8 control's widest gap, on the served tokens' positions, is
    several times the bf16 engine's (here at smoke widths)."""
    import jax

    from bench import check, deploy
    from bench.reference import dense_lm

    doc = smoke.loaded(HOT)["config"]
    spec = dict(doc["models"]["olmo-1b"], dtype="bfloat16", hidden_size=256,
                num_attention_heads=4, num_key_value_heads=4, head_dim=64,
                intermediate_size=512, num_hidden_layers=4)
    cfg = deploy.model_config(spec)
    params = deploy.make_params(cfg, SEED, 0.05)
    log = []
    Engine = deploy.recording_engine_class()
    eng = Engine(cfg, params, max_len=256, arch="olmo-1b", log=log)
    eng.recording = True
    rng = np.random.default_rng(0)
    for S in (20, 33):
        eng.generate(rng.integers(16, 50304, size=(1, S)).astype(np.int32), max_new=40)
    with jax.default_matmul_precision("highest"):
        g = check.logit_gaps(dense_lm, spec, params, log, control=True)
    assert g["tokens"] == 80
    assert g["control"] > 3 * g["served"] > 0
