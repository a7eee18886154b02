"""Benchmark runner: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only t1,t5] \
        [--json-dir DIR]

Prints ``name,us_per_call,derived`` CSV (one row per measured cell).
``--json-dir`` additionally writes one machine-readable ``BENCH_<key>.json``
per module ({"module", "fast", "rows": [{name, us_per_call, derived}]}) —
the CI smoke workflow uploads these as artifacts so the perf trajectory
(t1 headline aggregate, t5 lookup scaling) is tracked per commit.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

from benchmarks import (
    f3_matching,
    f5_hit_miss,
    kernel_bench,
    s1_sim,
    t1_main,
    t2_cost_breakdown,
    t3_latency,
    t4_cache_size,
    t5_lookup_scalability,
    t6_fuzzy_threshold,
    t7_cold_start,
    t8_kv_prefix,
    t9_sensitivity,
    t10_speculative,
)
from repro.launch import compile_cache

MODULES = {
    "t1": t1_main,
    "t2": t2_cost_breakdown,
    "t3": t3_latency,
    "t4": t4_cache_size,
    "t5": t5_lookup_scalability,
    "t6": t6_fuzzy_threshold,
    "t7": t7_cold_start,
    "t8": t8_kv_prefix,
    "t10": t10_speculative,
    "f3": f3_matching,
    "f5": f5_hit_miss,
    "t9": t9_sensitivity,
    "kernels": kernel_bench,
    "s1": s1_sim,
}


def _write_json(json_dir: str, key: str, payload: dict) -> None:
    os.makedirs(json_dir, exist_ok=True)
    path = os.path.join(json_dir, f"BENCH_{key}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default="")
    ap.add_argument(
        "--json-dir", default="",
        help="also write one BENCH_<module>.json per module into this dir",
    )
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(MODULES)
    compile_cache.enable()

    print("name,us_per_call,derived")
    failures = 0
    for key, mod in MODULES.items():
        if key not in only:
            continue
        t0 = time.time()
        try:
            rows = list(mod.run(fast=args.fast))
        except Exception:
            failures += 1
            print(f"{key},0,{{\"error\": true}}")
            traceback.print_exc(file=sys.stderr)
            if args.json_dir:
                _write_json(args.json_dir, key,
                            {"module": key, "fast": args.fast, "error": True})
            print(f"# {key} done in {time.time()-t0:.1f}s", file=sys.stderr)
            continue
        for row in rows:
            print(row.csv())
        if args.json_dir:
            _write_json(
                args.json_dir, key,
                {
                    "module": key,
                    "fast": args.fast,
                    "rows": [dataclasses.asdict(r) for r in rows],
                },
            )
        print(f"# {key} done in {time.time()-t0:.1f}s", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
