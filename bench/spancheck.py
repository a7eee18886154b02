"""Check the program's spans against a benchmark run, and what they cost.

    python3 -m bench.spancheck --workload <cell> --seed <n> --seconds <s> --trace 1
    python3 -m bench.spancheck --workload <cell> --seed <n> --seconds <s> --trace 0 --tracer <0|1>

Runs one cell through ``bench.harness.run`` as ``bench.run`` does and
prints its result line with one more key:

* ``--trace 1``: ``clock``, how far the reduction's placement of each
  exported span (the program's clock plus the offset that the harness's
  ``bench.*`` annotations give) lies from the span's own event on the
  profiler's host plane, which a tracer on the host's clock writes: the
  median and the largest distance of the starts, in microseconds, over
  the spans that have such an event (``jit.compile`` has none).
* ``--trace 0``: ``tracing``, with ``--tracer 1`` a host-clock ``Tracer``
  installed over the whole window (no profiler), and without it none:
  the large planner's mean decode step over the window (the reading of
  ``decode_ms.big``), the number of spans recorded, and the compiles the
  program counted (the reading of ``compiles``).

Compare ``--tracer 1`` against ``--tracer 0`` on the same seed for the
cost of tracing when it is on.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def clock_offsets(xplane: str, spans) -> dict:
    """Distance from each exported span, placed as ``bench/trace.py``
    places it, to its own host event: spans and events of one name are
    paired in order where their counts agree, else each span with the
    nearest event."""
    from jax.profiler import ProfileData

    names = {s["name"] for s in spans}
    native, offsets = defaultdict(list), []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    native[e.name].append(e.start_ns)
                elif e.name.startswith("bench."):
                    t_ns = dict(e.stats).get("t_ns")
                    if t_ns is not None:
                        offsets.append(e.start_ns - float(t_ns))
    offset = statistics.median(offsets) if offsets else 0.0
    placed = defaultdict(list)
    for s in spans:
        if s["name"] in native:
            placed[s["name"]].append(s["start"] * 1e9 + offset)
    dist = []
    for name, starts in placed.items():
        ev = sorted(native[name])
        starts.sort()
        if len(ev) == len(starts):
            dist += [abs(a - b) for a, b in zip(starts, ev)]
            continue
        for a in starts:
            i = bisect.bisect_left(ev, a)
            dist.append(min(abs(a - ev[j]) for j in (i - 1, i) if 0 <= j < len(ev)))
    return {
        "spans": len(spans), "paired": len(dist),
        "median_us": statistics.median(dist) / 1e3 if dist else None,
        "max_us": max(dist) / 1e3 if dist else None,
        "by_name": {n: [len(placed[n]), len(native[n])] for n in sorted(native)},
    }


def run(loaded: dict, seed: int, seconds: float, trace: bool, tracer: bool, *,
        t_process: float, **run_kw) -> dict:
    """``harness.run`` of one cell with the check's key added to its result;
    ``run_kw`` goes to ``harness.run`` (tests pass their CPU options)."""
    from bench import harness, trace as trace_mod
    from repro.obs import InMemoryExporter, Tracer, use_tracer

    big = loaded["config"]["roles"]["large_planner"]
    extra = {}
    reduce, run_window = harness._Profiler.reduce, harness.run_window

    def reduce_and_compare(profiler):
        extra["clock"] = clock_offsets(trace_mod.find_xplane(profiler.dir), profiler.spans)
        return reduce(profiler)

    def window(agent, backend, by_arch, stream, seconds):
        exporter = InMemoryExporter()
        stats = by_arch[big].stats
        s0, n0 = stats.decode_s, stats.decode_tokens
        scope = use_tracer(Tracer(exporters=[exporter])) if tracer else contextlib.nullcontext()
        with scope:
            out = run_window(agent, backend, by_arch, stream, seconds)
        n = stats.decode_tokens - n0
        extra["tracing"] = {"tracer": tracer, "spans": len(exporter.spans),
                            "decode_ms.big": 1e3 * (stats.decode_s - s0) / n if n else None,
                            "compiles": sum(r.compiles for r in out[0])}
        return out

    if trace:
        harness._Profiler.reduce = reduce_and_compare
    else:
        harness.run_window = window
    try:
        result = harness.run(loaded["cell"]["name"], seed, seconds, trace,
                             t_process=t_process, loaded=loaded, **run_kw)
    finally:
        harness._Profiler.reduce, harness.run_window = reduce, run_window
    result.update(extra)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness

    try:
        result = run(harness.load_cell(args.workload), args.seed, args.seconds,
                     bool(args.trace), bool(args.tracer), t_process=T_PROCESS)
    except harness.RefusedDevice as e:
        return e.code
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
