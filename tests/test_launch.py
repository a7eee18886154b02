"""Launch layer: input specs for all cells, serve pipeline on JAX engines."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import SHAPES
from repro.launch.specs import input_specs
from repro.models import lm


@pytest.mark.parametrize("arch,shape_name", registry.all_cells())
def test_input_specs_all_cells(arch, shape_name):
    cfg = registry.get(arch)
    shape = SHAPES[shape_name]
    batch, cache = input_specs(cfg, shape)
    if shape.kind == "decode":
        assert batch["tokens"].shape == (shape.global_batch, 1)
        assert cache is not None and "length" in cache
        if cfg.mla is not None:  # one latent row per position and layer
            L, B, M, C = cache["kv_latent"].shape
            assert (L, B, C) == (cfg.num_layers, shape.global_batch, cfg.mla.cache_dim)
            assert M >= shape.seq_len
        elif cfg.family in ("dense", "moe", "vlm"):
            L, B, M, H, hd = cache["kv_k"].shape
            assert (L, B, H, hd) == (
                cfg.num_layers, shape.global_batch, cfg.num_kv_heads, cfg.head_dim
            )
            assert M >= shape.seq_len
        if cfg.family in ("ssm", "hybrid"):
            assert "ssm_state" in cache
    else:
        assert cache is None
        if cfg.family == "vlm":
            assert batch["embeds"].shape == (
                shape.global_batch, shape.seq_len, cfg.d_model
            )
            assert batch["positions"].shape == (3, shape.global_batch, shape.seq_len)
        elif cfg.family == "audio":
            assert batch["frames"].shape[1] == cfg.encoder.num_frames
        else:
            assert batch["tokens"].shape == (shape.global_batch, shape.seq_len)
        if shape.kind == "train":
            assert "labels" in batch


@pytest.mark.parametrize("arch", ["rwkv6-3b", "zamba2-2.7b"])
def test_engine_generates_with_state_caches(arch, rng_key):
    """SSM/hybrid archs generate through the engine (state carried, no KV)."""
    from repro.serving.engine import Engine

    cfg = registry.get_smoke(arch)
    params = lm.init_params(cfg, rng_key)
    eng = Engine(cfg, params, max_len=48)
    toks = np.random.RandomState(0).randint(3, 400, (2, 10)).astype(np.int32)
    out = eng.generate(toks, max_new=6)
    assert out.shape == (2, 6)
    assert (out >= 0).all()


def test_jax_backend_serves_apc_end_to_end(rng_key):
    from repro.configs.apc_minion import DEFAULT
    from repro.core.agent_loop import AgentConfig, PlanActAgent
    from repro.core.cost_model import CostLedger
    from repro.envs.workloads import get_env
    from repro.serving.engine import Engine
    from repro.serving.jax_backend import JaxBackend

    cfg = registry.get_smoke("olmo-1b")
    params = lm.init_params(cfg, rng_key)
    eng = Engine(cfg, params, max_len=96)
    engines = {r: eng for r in
               ("large_planner", "small_planner", "actor", "keyword_extractor")}
    backend = JaxBackend(engines, seed=0, max_exec_tokens=4)
    ledger = CostLedger(pricing_map=dict(DEFAULT.pricing))
    agent = PlanActAgent(backend, ledger, AgentConfig(method="apc"))
    tasks = get_env("tabmwp").generate(6, seed=0)
    recs = [agent.run_task(t) for t in tasks]
    assert len(recs) == 6
    assert eng.stats.decode_tokens > 0  # real data-plane tokens served
    assert ledger.total_cost() > 0
