"""Reduce a profiler trace (``.xplane.pb``) to the per-layer readings.

* Busy time: the union of the intervals in which an operation ran on each
  device (the ``XLA Ops`` line of every ``/device:TPU:N`` plane), averaged
  over the devices that ran any; idle is the traced window less that.
* Program executions: the ``XLA Modules`` line, one event per run of a
  jitted program, named ``<program>(<fingerprint>)``. Each is tied to the
  host by its ``run_id``: the host's ``CompleteCallbacks`` event of the same
  run lands inside the host call that waited for it.
* Host spans: the harness's ``bench.*`` annotations carry the host clock
  they began at (``t_ns``), which places them, and the program's own spans
  (taken on that host clock), on the profiler's clock.
* Device operations: seconds per (program, op), control-flow ops that
  only contain others (a scanned layer loop's ``while``) left out.
* Idle gaps: each stretch in which no operation ran, shifted to the host's
  clock, is put down to the innermost host span around its middle.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import statistics
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

OUTSIDE = "outside any span"
# control-flow ops whose events span the ops nested in them
_CONTAINERS = ("while", "conditional", "call")


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, found {paths}")
    return paths[0]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _base(name: str) -> str:
    return name.split("(", 1)[0]


@dataclasses.dataclass
class Execution:
    program: str  # jitted program, fingerprint stripped
    start: float  # device clock, ns
    dur: float  # ns
    host_done: Optional[float]  # host clock (profiler's), ns


@dataclasses.dataclass
class HostSpan:
    name: str
    start: float  # profiler clock, ns
    end: float
    attrs: dict


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    n_devices: int
    executions: List[Execution]
    host: List[HostSpan]
    op_seconds: Dict[str, float]
    gap_seconds: Dict[str, float]

    def spans(self, name: str) -> List[HostSpan]:
        return [h for h in self.host if h.name == name]

    def executions_in(self, span: HostSpan, program: str) -> List[Execution]:
        """Runs of ``program`` that the host saw complete inside ``span``."""
        return sorted(
            (x for x in self.executions
             if x.program == program and x.host_done is not None
             and span.start <= x.host_done <= span.end),
            key=lambda x: x.start,
        )

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]

        return {"device_ops": top(self.op_seconds), "idle_gaps": top(self.gap_seconds)}


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def reduce(path: str, *, window_s: float, t_start: float, spans: Sequence[dict] = ()) -> Reduction:
    """``t_start``: host clock (seconds) at which the traced window began;
    ``spans``: the program's spans (``repro.obs`` dicts) on that clock."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    execs: List[Tuple[str, float, float, Optional[int]]] = []
    done: Dict[int, float] = {}
    host: List[HostSpan] = []
    offsets: List[float] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        ops[plane.name].append((e.start_ns, e.start_ns + e.duration_ns, e.name))
                elif line.name == "XLA Modules":
                    for e in line.events:
                        rid = _stat(e, "run_id")
                        execs.append((e.name, e.start_ns, e.duration_ns,
                                      None if rid is None else int(rid)))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    name = e.name
                    if name == "CompleteCallbacks":
                        rid = _stat(e, "run_id")
                        if rid is not None:
                            done.setdefault(int(rid), e.start_ns)
                    elif name.startswith("bench."):
                        attrs = {k: v for k, v in e.stats}
                        if "t_ns" in attrs:
                            offsets.append(e.start_ns - float(attrs["t_ns"]))
                        host.append(HostSpan(name, e.start_ns, e.start_ns + e.duration_ns, attrs))
    offset = statistics.median(offsets) if offsets else 0.0
    for s in spans:
        if s.get("end") is not None:
            host.append(HostSpan(s["name"], s["start"] * 1e9 + offset, s["end"] * 1e9 + offset,
                                 dict(s.get("attrs") or {})))
    executions = [Execution(_base(n), st, d, done.get(r)) for n, st, d, r in execs]
    # device clock -> host clock: the median lag from a run's end to the
    # host's completion callback (it also holds the callback's own latency)
    lags = [x.host_done - (x.start + x.dur) for x in executions if x.host_done is not None]
    skew = statistics.median(lags) if lags else 0.0

    w0 = t_start * 1e9 + offset
    w1 = w0 + window_s * 1e9
    busy, op_seconds, gap_seconds = 0.0, defaultdict(float), defaultdict(float)
    mods = sorted((x.start, x.start + x.dur, x.program) for x in executions)
    mod_starts = [m[0] for m in mods]
    host_sorted = sorted(host, key=lambda h: h.start)
    for plane, evs in ops.items():
        for s, e, name in evs:
            i = bisect.bisect_right(mod_starts, s) - 1
            prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            op = name.split(" = ", 1)[0].lstrip("%")
            if op.split(".")[0] not in _CONTAINERS:  # their nested ops are listed
                op_seconds[f"{prog}/{op}"] += (e - s) / 1e9
        merged = _union([(s + skew, e + skew) for s, e, _ in evs])
        merged = [(max(s, w0), min(e, w1)) for s, e in merged if e > w0 and s < w1]
        busy += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gap_seconds[_label(host_sorted, (a + b) / 2)] += (b - a) / 1e9
    n_dev = max(1, len(ops))
    return Reduction(
        window_s=window_s, busy_s=busy / n_dev / 1e9, n_devices=len(ops),
        executions=executions, host=host,
        op_seconds={k: v / n_dev for k, v in op_seconds.items()},
        gap_seconds={k: v / n_dev for k, v in gap_seconds.items()},
    )


def _label(host_sorted: List[HostSpan], t: float) -> str:
    """The innermost (latest-starting) host span that contains ``t``."""
    best = None
    for h in host_sorted:
        if h.start > t:
            break
        if h.end >= t:
            best = h
    return OUTSIDE if best is None else best.name
