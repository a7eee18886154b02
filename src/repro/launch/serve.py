"""Serving entry point: APC two-tier agent serving on real JAX engines.

    python -m repro.launch.serve --env financebench --n 40 --method apc
    python -m repro.launch.serve --full --deployment one-chip

Runs the paper's pipeline end-to-end: keyword extraction -> plan-cache
routing -> small/large planner tier -> actor, with every control-plane call
executed on a JAX engine (reduced configs by default, published widths with
``--full``), and prints the paper's headline metrics (cost, accuracy,
modelled latency, hit rate).

Each engine holds its whole model on the default device; nothing here
builds a mesh or spreads a model over chips. So ``--full`` needs every
tier to fit one device: the ``one-chip`` deployment (olmo-1b + qwen2.5-3b,
about 9.1 GB of bf16 weights) fits one TPU v5e (16 GB HBM); the
``default`` deployment does not, since its large planner nemotron-4-15b
alone is 31.3 GB in bf16.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
import zlib
from functools import partial
from typing import Callable, Dict, Optional

import jax

from repro.configs import registry
from repro.configs.apc_minion import DEFAULT, ONE_CHIP, APCDeployment
from repro.core.agent_loop import AgentConfig, PlanActAgent
from repro.core.cost_model import CostLedger
from repro.envs.workloads import get_env
from repro.launch import compile_cache
from repro.models import lm
from repro.serving.engine import Engine
from repro.serving.jax_backend import JaxBackend

DEPLOYMENTS: Dict[str, APCDeployment] = {"default": DEFAULT, "one-chip": ONE_CHIP}

ROLES = ("large_planner", "small_planner", "actor", "keyword_extractor")


def build_engines(deployment: APCDeployment, *, full: bool = False,
                  max_len: int = 192) -> Dict[str, Engine]:
    """role -> Engine; roles that share an architecture share one engine.

    Weights are random, seeded by a stable digest of the architecture's
    name (not ``hash()``, which Python salts per process), so every process
    builds the same ones.
    """
    engines: Dict[str, Engine] = {}
    built: Dict[str, Engine] = {}
    for role in ROLES:
        arch = getattr(deployment, role)
        if arch not in built:
            cfg = registry.get(arch) if full else registry.get_smoke(arch)
            key = jax.random.PRNGKey(zlib.crc32(arch.encode()) % 2**31)
            # one jitted program: no f32 transients left on the device
            params = jax.jit(partial(lm.init_params, cfg))(key)
            built[arch] = Engine(cfg, params, max_len=max_len)
        engines[role] = built[arch]
    return engines


@dataclasses.dataclass
class ServeReport:
    tasks: int
    hits: int
    misses: int
    accuracy: float
    tokens: Dict[str, int]  # arch -> prefill + decode tokens run in this call
    cost: float  # $ at the paper's Table 8 prices
    modelled_latency_s: float  # cost-model seconds, not a timing
    cache_entries: int


def serve(deployment: APCDeployment, *, env: str = "financebench",
          n: int = 40, method: str = "apc", full: bool = False,
          engines: Optional[Dict[str, Engine]] = None,
          log: Callable[[str], None] = print) -> ServeReport:
    """Serve ``n`` tasks of ``env`` through the APC agent on JAX engines.

    The plan cache follows the deployment (capacity, fuzzy matching and
    its threshold, index backend). ``engines`` reuses engines from
    :func:`build_engines`; without it they are built here.
    """
    if engines is None:
        engines = build_engines(deployment, full=full)
    by_arch = {e.cfg.name: e for e in engines.values()}

    def tokens_run():
        return {name: e.stats.prefill_tokens + e.stats.decode_tokens
                for name, e in by_arch.items()}

    tokens_before = tokens_run()
    backend = JaxBackend(engines, seed=0)
    ledger = CostLedger(pricing_map=dict(deployment.pricing))
    agent = PlanActAgent(backend, ledger, AgentConfig(
        method=method,
        max_iterations=deployment.max_iterations,
        cache_capacity=deployment.cache_capacity,
        fuzzy=deployment.fuzzy_matching,
        fuzzy_threshold=deployment.fuzzy_threshold,
        index_backend=deployment.index_backend,
    ))
    tasks = get_env(env).generate(n, seed=0)
    correct = hits = 0
    for i, t in enumerate(tasks):
        rec = agent.run_task(t)
        correct += rec.correct
        hits += rec.hit
        if (i + 1) % 10 == 0:
            log(f"[serve] {i+1}/{n} acc={correct/(i+1):.2f} "
                f"hit={hits/(i+1):.2f} cost=${ledger.total_cost():.3f}")
    return ServeReport(
        tasks=len(tasks),
        hits=hits,
        misses=len(tasks) - hits,
        accuracy=correct / max(1, len(tasks)),
        tokens={name: n_after - tokens_before[name]
                for name, n_after in tokens_run().items()},
        cost=ledger.total_cost(),
        modelled_latency_s=ledger.total_latency(),
        cache_entries=len(agent.cache),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--env", default="financebench")
    ap.add_argument("--n", type=int, default=40)
    ap.add_argument("--method", default="apc")
    ap.add_argument("--full", action="store_true",
                    help="published widths (needs an accelerator)")
    ap.add_argument("--deployment", choices=sorted(DEPLOYMENTS),
                    default="default")
    ap.add_argument("--cache-capacity", type=int, default=None)
    args = ap.parse_args()

    compile_cache.enable()
    deployment = DEPLOYMENTS[args.deployment]
    if args.cache_capacity is not None:
        deployment = dataclasses.replace(
            deployment, cache_capacity=args.cache_capacity
        )
    print(f"[serve] tiers: large={deployment.large_planner} "
          f"small={deployment.small_planner} actor={deployment.actor} "
          f"({'published widths' if args.full else 'reduced configs'} on "
          f"{jax.devices()[0].device_kind})")
    t0 = time.perf_counter()
    rep = serve(deployment, env=args.env, n=args.n, method=args.method,
                full=args.full)
    wall = time.perf_counter() - t0
    print(f"[serve] method={args.method} n={rep.tasks}")
    print(f"  accuracy      {rep.accuracy:.3f}")
    print(f"  hit rate      {rep.hits / max(1, rep.tasks):.3f}")
    print(f"  cost          ${rep.cost:.4f}  (paper Table 8 prices)")
    print(f"  modelled lat. {rep.modelled_latency_s:.1f}s  (cost model)")
    print(f"  wall          {wall:.1f}s  (compilation included)")
    print(f"  tokens        {rep.tokens}")
    print(f"  cache entries {rep.cache_entries}")


if __name__ == "__main__":
    main()
