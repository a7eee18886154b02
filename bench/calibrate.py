"""Readings that the limits of ``correct`` are set from (run on the chip).

    python3 -m bench.calibrate --workload <cell> --seeds 12 --seconds 20

In one process, one ``bench.harness.run`` per seed with a short window of
the cell's own traffic, with the fp8 control put in the engines' place in
its check: the same set-up, window, sample of finished calls and limits as
a benchmark run. Each run gives, per architecture compared, the widest gap
of the served tokens (the program's reading) and that of the tokens the
control puts first (the control's reading), and ``correct`` as the
control makes it: false where the limits separate the two. One JSON line
per seed. A limit lies between the program's largest reading and the
control's smallest.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2**33 + 5000)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    from bench import harness

    for k in range(args.seeds):
        seed = args.first_seed + k
        r = harness.run(args.workload, seed, args.seconds, False,
                        t_process=time.perf_counter(), control=True)
        print(json.dumps({"workload": args.workload, "seed": seed, "correct": r["correct"],
                          "tasks": r["window"]["tasks"], "failed": r["failed"],
                          "window_s": r["window"]["seconds"], "checks": r["checks"]}),
              flush=True)
        del r
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
