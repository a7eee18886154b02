"""The benchmark's core: one cell, one run.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run reads its cell from ``BENCHMARK.json``, its configuration from
``bench/configs/<config>.json`` and its traffic from
``bench/traffic/<traffic>.json``, the limits of its checks from
``bench/limits/<cell>.json``, and the reader of each metric it reports
from ``bench/metrics/<metric>.py``. Adding a cell, a configuration, a
traffic mix or a metric adds files and entries; nothing here names one.

Set-up (``setup_s``, from the process's start): the device gate, the
compile cache, the weights (on the device, from the seed), the engines, the
plan caches (prewarmed on the simulated backend), and the shapes the
stream will use: one prefill per distinct (model, prompt length), one
two-token ``generate`` per engine for the decode step. Then the window:
one closed-loop client runs ``PlanActAgent.run_task`` over the stream
until ``--seconds`` have passed, finishing the task in flight, so the
window ends at a task boundary. Afterwards the program is freed and the
outputs are checked (``bench/check.py``).

With ``--trace 1`` the profiler records the window's first
``TRACE_SECONDS`` (to the end of the role call in flight), with the
program's spans and the harness's own annotations; the metrics printed
are the cell's per-layer metrics.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import logging
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from bench import check, deploy, flops, traffic
from bench.peaks import peaks

ROOT = Path(__file__).resolve().parent.parent
BENCH = Path(__file__).resolve().parent
TRACE_SECONDS = 5.0
# served tokens each architecture's check compares, and its most calls
CHECK_TOKENS = 400
CHECK_ROWS = 6

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class RefusedDevice(SystemExit):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def note(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_cell(name: str, root: Path = ROOT) -> dict:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = next(c for c in spec["configs"] if c["name"] == cell["config"])

    def reports(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "root": root,
        "cell": cell,
        "config": json.loads((root / cfg["file"]).read_text()),
        "traffic": traffic.load(cell["traffic"], root / "bench" / "traffic"),
        "limits": json.loads((root / "bench" / "limits" / f"{name}.json").read_text()),
        "end_to_end": [m for m in spec["end_to_end"] if reports(m)],
        "per_layer": [m for m in spec["per_layer"] if reports(m)],
    }


def device_gate(chips: int):
    """The default device must be a TPU, with as many chips as the cell asks."""
    import jax

    devs = jax.devices()
    d = devs[0]
    log(f"device platform={d.platform} kind={d.device_kind} count={len(devs)}")
    if d.platform != "tpu" or len(devs) < chips:
        note(f"refused: the cell needs {chips} TPU chip(s); found {len(devs)} "
             f"{d.platform} device(s)")
        raise RefusedDevice(3)
    return devs


class CompileCounter(logging.Handler):
    """Counts XLA compilations; between ``watch()`` and ``unwatch()`` it
    also keeps what was compiled (JAX's compile log)."""

    def __init__(self):
        import jax

        super().__init__()
        self.n, self.seconds, self.what = 0, 0.0, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.n += 1
            self.seconds += duration

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.what.append(msg[len("Compiling "):].split(". Argument mapping")[0][:300])

    def watch(self):
        import jax

        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)

    def unwatch(self):
        import jax

        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", False)


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT):
    """The ``read(run)`` of ``bench/metrics/<name>.py``."""
    return _module(root / "bench" / "metrics" / f"{name}.py", f"bench_metric_{name}").read


@dataclasses.dataclass
class EngineDelta:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    prefill_tokens: int = 0
    decode_tokens: int = 0


@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read of one run."""

    config: dict
    traffic: dict
    device_kind: str
    setup_s: float
    window_s: float
    records: list  # RunRecord per task completed in the window
    turns: list  # (role, seconds) per role call in the window
    calls: list  # deploy.Call per engine.generate in the window
    engines: Dict[str, EngineDelta]  # arch -> stats over the window
    profiler_s: float = 0.0  # profiler start and stop inside the window
    trace: Optional[object] = None  # bench.trace.Reduction of a traced run

    @property
    def big(self) -> str:
        return self.config["roles"]["large_planner"]

    @property
    def small(self) -> str:
        return self.config["roles"]["small_planner"]

    @property
    def peaks(self) -> dict:
        return peaks(self.device_kind)

    def model(self, arch: str) -> dict:
        return self.config["models"][arch]

    def flops(self) -> int:
        return sum(flops.generate_flops(self.model(c.arch), len(c.prompt), len(c.out))
                   for c in self.calls)


def warm_up(doc, tr, prewarm, stream, by_arch) -> int:
    """Compile every prompt length the stream will prefill, and the decode
    step; returns the number of (model, prompt length) shapes."""
    shapes = deploy.prompt_shapes(doc, tr, prewarm, stream)
    for arch, S in sorted(shapes):
        by_arch[arch].generate(np.ones((1, S), np.int32), max_new=1)
    for eng in by_arch.values():
        eng.generate(np.ones((1, 8), np.int32), max_new=2)
    return len(shapes)


def run_window(agent, backend, by_arch, stream, seconds: float):
    """Closed loop over the stream until ``seconds`` pass; returns
    (records, lookups, t_start, t_end, failed)."""
    records, lookups, failed = [], [], 0
    cache = agent.cache
    served = []
    inner = cache.lookup

    def lookup(kw, **kw_):
        tpl = inner(kw, **kw_)
        served.append(None if tpl is None else tpl.keyword)
        return tpl

    cache.lookup = lookup
    for eng in by_arch.values():
        eng.recording = True
    backend.recording = True
    t0 = time.perf_counter()
    deadline = t0 + seconds
    try:
        for task in stream:
            served.clear()
            try:
                rec = agent.run_task(task)
            except Exception as e:  # a failed task counts; the window goes on
                failed += 1
                note(f"task {task.id} failed: {type(e).__name__}: {e}")
                rec = None
            if rec is not None:
                records.append(rec)
                lookups.append(check.Lookup(
                    rec.keyword, served[0] if served else None,
                    rec.answer is not None, rec.keyword in cache))
            if time.perf_counter() >= deadline:
                break
        else:
            note(f"the stream ran out after {len(stream)} tasks; the window ends there")
    finally:
        t1 = time.perf_counter()
        for eng in by_arch.values():
            eng.recording = False
        backend.recording = False
        del cache.lookup
    return records, lookups, t0, t1, failed


def _stats(by_arch):
    return {a: dataclasses.asdict(e.stats) for a, e in by_arch.items()}


def run(workload: str, seed: int, seconds: float, trace: bool, *, t_process: float,
        loaded: Optional[dict] = None, require_tpu: bool = True, persistent_cache: bool = True,
        faults=(), control: bool = False) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``loaded`` replaces the files of the cell (tests pass smaller sizes);
    ``require_tpu=False`` skips the device gate and ``persistent_cache=False``
    the compile cache (tests on the CPU);
    ``faults`` are callables applied to (engines, agent) before the window,
    for the test that sees ``correct`` turn false;
    ``control`` puts the fp8 control in the engines' place in the check
    (``bench/calibrate.py`` and the tests; a benchmark run never does)."""
    loaded = loaded or load_cell(workload)
    cell, doc, tr = loaded["cell"], loaded["config"], loaded["traffic"]
    import jax

    devs = device_gate(cell["chips"]) if require_tpu else jax.devices()
    device = devs[0]
    from repro.launch import compile_cache

    if persistent_cache:
        log(f"compile cache {compile_cache.enable()}")
    compiles = CompileCounter()

    marks = [("start", time.perf_counter())]
    params = {a: deploy.make_params(deploy.model_config(m), seed, doc["weight_std"])
              for a, m in doc["models"].items()}
    jax.block_until_ready(params)
    marks.append(("weights", time.perf_counter()))
    calls: List[deploy.Call] = []
    engines, by_arch = deploy.build_engines(doc, params, calls)
    prewarm, stream = traffic.generate(tr, seed)
    cache = deploy.prewarmed_cache(doc, tr, prewarm)
    keys_before = list(cache.keys())
    marks.append(("plan cache", time.perf_counter()))
    n_shapes = warm_up(doc, tr, prewarm, stream, by_arch)
    marks.append(("shapes", time.perf_counter()))
    agent, backend = deploy.agent(doc, tr, engines, cache, max_exec=doc["max_exec_tokens"])
    for fault in faults:
        fault(by_arch, agent)
    stats0 = _stats(by_arch)
    setup_s = time.perf_counter() - t_process
    phases = ", ".join(f"{b[0]} {b[1] - a[1]:.3f}s" for a, b in zip(marks, marks[1:]))
    log(f"setup {setup_s:.3f}s (imports and gate {marks[0][1] - t_process:.3f}s, {phases}): "
        f"{len(keys_before)} templates prewarmed, {n_shapes} prompt shapes warmed, "
        f"{compiles.n} compilations ({compiles.seconds:.3f}s)")

    profiler = _Profiler(by_arch, backend) if trace else None
    n0, s0 = compiles.n, compiles.seconds
    compiles.watch()
    if profiler:
        profiler.start()
    try:
        records, lookups, t0, t1, failed = run_window(agent, backend, by_arch, stream, seconds)
    finally:
        compiles.unwatch()
    if profiler:
        profiler.stop(inside=False)  # no-op when it stopped inside the window
    window_s = t1 - t0
    stats1 = _stats(by_arch)
    in_window = compiles.n - n0
    log(f"window {window_s:.3f}s: {len(records)} tasks, {failed} failed, "
        f"{sum(r.hit for r in records)} plan-cache hits, {len(backend.turns)} turns, "
        f"{len(calls)} generate calls; compilations inside the window: {in_window} "
        f"({compiles.seconds - s0:.3f}s)")
    for what in compiles.what:
        log(f"compiled inside the window: {what}")
    mem = device.memory_stats() or {}
    run_data = RunData(
        config=doc, traffic=tr, device_kind=device.device_kind, setup_s=setup_s,
        window_s=window_s, records=records, turns=list(backend.turns), calls=list(calls),
        engines={a: EngineDelta(**{k: stats1[a][k] - stats0[a][k]
                                   for k in dataclasses.asdict(EngineDelta())})
                 for a in by_arch},
    )
    if profiler:
        run_data.profiler_s = profiler.overhead_s
        run_data.trace = profiler.reduce()
        profiler = None

    metrics = {}
    for m in (loaded["per_layer"] if trace else loaded["end_to_end"]):
        v = reader(m["name"], loaded["root"])(run_data)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # free the program before the references run
    cache_fuzzy = agent.cache.fuzzy
    del agent, backend, engines, by_arch, cache
    gc.collect()

    checks = _checks(doc, tr, params, run_data, lookups, keys_before, prewarm, stream, seed,
                     cache_fuzzy, reference_module(doc["reference"], loaded["root"]),
                     loaded["limits"], control)
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    result = {
        "correct": correct,
        "attempted": len(records) + failed,
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(devs), "memory_peak_bytes": mem.get("peak_bytes_in_use", 0)},
    }
    if run_data.trace is not None:
        result["device"]["busy_s"] = run_data.trace.busy_s
        result["device"]["window_s"] = run_data.trace.window_s
        result["breakdown"] = run_data.trace.breakdown()
    result["window"] = {"seconds": window_s, "tasks": len(records),
                        "hits": sum(r.hit for r in records), "turns": len(run_data.turns),
                        "generate_calls": len(run_data.calls), "compilations": in_window}
    result["checks"] = checks
    return result


def _checks(doc, tr, params, run_data, lookups, keys_before, prewarm, stream, seed, fuzzy, ref,
            limits, control=False):
    """The compared numbers, each with its limit from ``bench/limits/<cell>.json``:
    the served-token gap of every architecture that has a limit there, and
    the plan cache's mismatches against the plain matcher and the replay.
    With ``control`` the gap compared is that of the tokens the fp8 control
    puts first at the same positions; both readings are logged."""
    checks = {}
    for arch, spec in doc["models"].items():
        if f"gap.{arch}" not in limits:
            continue
        picked = check.sample_calls(run_data.calls, arch, seed, CHECK_TOKENS, CHECK_ROWS)
        if not picked:
            continue
        g = check.logit_gaps(ref, spec, params[arch], picked, control=control)
        log(f"check {arch}: {len(picked)} calls, {g['tokens']} served tokens, "
            f"widest gap {g['served']!r}"
            + (f", fp8 control's widest gap {g['control']!r}" if control else ""))
        checks[f"gap.{arch}"] = {"value": g["control" if control else "served"],
                                 "limit": limits[f"gap.{arch}"]}
        if control:
            checks[f"gap.{arch}"]["served"] = g["served"]
    bad, notes = check.cache_mismatches(keys_before, lookups, doc["plan_cache"]["fuzzy_threshold"], fuzzy)
    for n in notes[:10]:
        note(f"plan cache: {n}")
    checks["cache_mismatch"] = {"value": bad, "limit": limits["cache_mismatch"]}
    checks["replay_mismatch"] = {"value": _replay_mismatches(doc, tr, prewarm, stream, run_data.records),
                                 "limit": limits["replay_mismatch"]}
    return checks


def _replay_mismatches(doc, tr, prewarm, stream, records) -> int:
    """The window's tasks again on the simulated backend with the exact
    ``brute`` index: hits, keywords and answers must repeat."""
    from repro.core.agent_loop import PlanActAgent
    from repro.core.backends import SimulatedBackend

    cfg = deploy.agent_config(doc)
    cfg.index_backend = "brute"
    cache = deploy.prewarmed_cache(doc, tr, prewarm, index_backend="brute")
    agent = PlanActAgent(SimulatedBackend(seed=tr["semantic_seed"]), deploy.ledger(), cfg,
                         cache=cache)
    bad = 0
    for task, rec in zip(stream, records):
        r = agent.run_task(deploy._fresh(task))
        if (r.hit, r.keyword, r.answer) != (rec.hit, rec.keyword, rec.answer):
            bad += 1
            note(f"replay: task {task.id} served (hit={rec.hit}, {rec.keyword!r}, "
                 f"{rec.answer}) but replays (hit={r.hit}, {r.keyword!r}, {r.answer})")
    return bad


def reference_module(name: str, root: Path = ROOT):
    return _module(root / "bench" / "reference" / f"{name}.py", f"bench_reference_{name}")


class _Profiler:
    """Profiles the window's first ``TRACE_SECONDS`` (to the end of the role
    call in flight), with the harness's annotations and the program's spans."""

    def __init__(self, by_arch, backend):
        self.by_arch, self.backend = by_arch, backend
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.spans: list = []
        self.overhead_s = 0.0
        self.t_start = self.t_stop = None
        self.on = False

    def start(self):
        import jax
        from repro.obs import InMemoryExporter, Tracer, set_tracer

        self.exporter = InMemoryExporter()
        self.prev = set_tracer(Tracer(exporters=[self.exporter]))
        for e in self.by_arch.values():
            e.annotate = True
        self.backend.annotate = True
        self.backend.after_turn = self._maybe_stop
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        t = time.perf_counter()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()
        self.overhead_s += self.t_start - t
        self.on = True

    def _maybe_stop(self):
        if self.on and time.perf_counter() - self.t_start >= TRACE_SECONDS:
            self.stop()

    def stop(self, inside: bool = True):
        if not self.on:
            return
        import jax
        from repro.obs import set_tracer

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        if inside:
            self.overhead_s += time.perf_counter() - self.t_stop
        self.on = False
        set_tracer(self.prev)
        for e in self.by_arch.values():
            e.annotate = False
        self.backend.annotate = False
        self.backend.after_turn = None
        self.spans = list(self.exporter.spans)

    def reduce(self):
        from bench import trace as trace_mod

        try:
            path = trace_mod.find_xplane(self.dir)
            return trace_mod.reduce(path, window_s=self.t_stop - self.t_start,
                                    t_start=self.t_start, spans=self.spans)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def print_result(result: dict) -> None:
    for name, c in result["checks"].items():
        note(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
