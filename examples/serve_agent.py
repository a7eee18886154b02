"""End-to-end driver: serve a small model with batched requests through the
full APC serving stack (keyword extraction, plan-cache routing, two-tier
planners, actor) running REAL JAX engines.

    PYTHONPATH=src python examples/serve_agent.py [--n 30] [--env tabmwp]

This is the paper's deployment in miniature: every control-plane LM call is
executed on a JAX model, and the cache decides which tier serves each
request. It calls :func:`repro.launch.serve.serve` on the default
deployment, with reduced configs unless ``--full`` asks for published
widths; at those widths the default deployment's large planner
(nemotron-4-15b, 31.3 GB in bf16) needs more than one TPU v5e. For the
deployment that fits one chip, run
``python -m repro.launch.serve --full --deployment one-chip``.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.configs.apc_minion import DEFAULT
from repro.launch.serve import serve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=30)
    ap.add_argument("--env", default="tabmwp")
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()

    widths = "published widths" if args.full else "reduced configs"
    print(f"tiers: large={DEFAULT.large_planner}  small={DEFAULT.small_planner}  "
          f"actor={DEFAULT.actor}  ({widths}, {len(jax.devices())} device)")
    t0 = time.time()
    rep = serve(DEFAULT, env=args.env, n=args.n, full=args.full)
    print(f"\nn={rep.tasks}  accuracy={rep.accuracy:.2f}  "
          f"hit_rate={rep.hits / max(1, rep.tasks):.2f}  cost=${rep.cost:.3f}  "
          f"wall={time.time()-t0:.1f}s")
    print("engine tokens served:", rep.tokens)


if __name__ == "__main__":
    main()
