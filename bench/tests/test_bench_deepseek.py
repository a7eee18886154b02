"""Smoke runs of the DeepSeek-V2-Lite cell on the CPU: a sound run is
correct, the fp8 control and two faults planted in the expert layer and
the latent cache are not, and the routed-pair counter adds up."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench.tests import smoke  # noqa: E402
from bench.tests.test_bench_run import _run, cpu_peaks  # noqa: E402,F401

CELL = "deepseek-v2-lite.cold-unique"
ARCH = "deepseek-v2-lite"


def test_sound_run_is_correct_and_counts_every_routed_pair():
    """Every token a model on the dropless expert path processes routes to
    experts_per_token experts in each MoE layer: the engine's counter sums
    to that times the tokens it prefilled and decoded, warm-up included."""
    seen = {}
    r = _run(smoke.loaded(CELL), faults=[lambda by_arch, agent: seen.update(by_arch)])
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0 and r["window"]["hits"] == 0
    assert r["window"]["compilations"] == 0
    assert set(r["metrics"]) >= {"task_s", "out_tok_per_s", "setup_s"}
    eng = seen[ARCH]
    cfg, st = eng.cfg, eng.stats
    moe_layers = cfg.num_layers - cfg.moe.first_k_dense
    assert st.expert_tokens.shape == (moe_layers, cfg.moe.num_experts)
    assert int(st.expert_tokens.sum()) == (cfg.moe.experts_per_token * moe_layers
                                           * (st.prefill_tokens + st.decode_tokens))


def test_traced_run_reads_the_expert_load():
    r = _run(smoke.loaded(CELL), trace=True)
    assert r["correct"], r["checks"]
    load = r["metrics"]["expert_load_max"]["value"]
    assert 100.0 / 64 <= load <= 100.0


def test_control_in_the_engines_place_is_not_correct():
    """The fp8 control's gap under the committed limit (at smoke widths the
    logits are of unit scale only with larger weights, as in the dense
    cells' test)."""
    r = _run(smoke.loaded(CELL, weight_std=0.4), control=True)
    assert not r["correct"], r["checks"]
    g = r["checks"][f"gap.{ARCH}"]
    assert g["value"] > g["limit"] > g["served"]


def _drop_an_expert(monkeypatch):
    """A routed expert's output dropped: the decode step recompiled with
    each token's first routed expert given a weight of zero."""
    import jax

    from repro.models import lm
    from repro.models import moe as moe_mod

    inner = moe_mod._routed_gather

    def routed_gather(cfg, p, x2d, topk_w, topk_idx, layer):
        return inner(cfg, p, x2d, topk_w.at[:, 0].set(0.0), topk_idx, layer)

    def fault(by_arch, agent):
        monkeypatch.setattr(moe_mod, "_routed_gather", routed_gather)
        eng = by_arch[ARCH]

        def decode_fn(params, cache, tokens):
            logits, cache = lm.decode_step(eng.cfg, params, cache, tokens)
            return logits[:, -1], cache

        eng._decode = jax.jit(decode_fn)

    return fault


def _stale_latent_cache(monkeypatch):
    """A decode step that returns the latent cache unchanged (the length and
    the routed-pair counts move on)."""

    import jax.numpy as jnp

    def fault(by_arch, agent):
        eng = by_arch[ARCH]
        inner = eng._decode

        def decode(params, cache, tokens):
            stale = jnp.array(cache["kv_latent"], copy=True)  # the step donates its cache
            logits, new = inner(params, dict(cache), tokens)
            return logits, {**new, "kv_latent": stale}

        eng._decode = decode

    return fault


@pytest.mark.parametrize("fault", [_drop_an_expert, _stale_latent_cache],
                         ids=["expert-dropped", "latent-cache-unchanged"])
def test_a_fault_in_the_new_layers_turns_correct_false(fault, monkeypatch):
    r = _run(smoke.loaded(CELL, weight_std=0.4), faults=[fault(monkeypatch)])
    assert not r["correct"], r["checks"]
    assert r["failed"] == 0
    assert r["checks"][f"gap.{ARCH}"]["value"] > r["checks"][f"gap.{ARCH}"]["limit"]
