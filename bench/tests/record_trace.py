"""Record the small trace that ``test_bench_trace.py`` reduces.

    python3 -m bench.tests.record_trace   # on a machine with a TPU

Two jitted programs named as the engine names its own (``prefill_fn``,
``decode_fn``) run under the harness's annotations, with the program's
span tracer on and host sleeps between the steps, so the trace holds known
idle gaps inside known host spans. Writes ``bench/tests/data/trace.xplane.pb``
and ``bench/tests/data/trace.json`` (window, program spans, step count).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

DATA = os.path.join(ROOT, "bench", "tests", "data")
STEPS = 4
SLEEP_S = 0.003


def main() -> int:
    import jax
    import jax.numpy as jnp

    from bench import trace as trace_mod
    from repro.obs import InMemoryExporter, Tracer, trace_span, use_tracer

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 1

    def prefill_fn(w, x):
        return jnp.tanh(x @ w)

    def decode_fn(w, x):
        return jnp.tanh(x @ w) * 2.0

    prefill, decode = jax.jit(prefill_fn), jax.jit(decode_fn)
    w = jnp.ones((2048, 2048), jnp.bfloat16)
    x = jnp.ones((8, 2048), jnp.bfloat16)
    prefill(w, x).block_until_ready()
    decode(w, x).block_until_ready()
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    exporter = InMemoryExporter()
    with use_tracer(Tracer(exporters=[exporter])):
        jax.profiler.start_trace(d, profiler_options=opts)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.act", t_ns=int(t0 * 1e9)):
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.generate", arch="toy", S=8, n=STEPS + 1,
                                              t_ns=int(t1 * 1e9)):
                with trace_span("engine.generate"):
                    prefill(w, x).block_until_ready()
                    for _ in range(STEPS):
                        time.sleep(SLEEP_S)
                        decode(w, x).block_until_ready()
        time.sleep(SLEEP_S)
        t_end = time.perf_counter()
        jax.profiler.stop_trace()
    os.makedirs(DATA, exist_ok=True)
    shutil.copy(trace_mod.find_xplane(d), os.path.join(DATA, "trace.xplane.pb"))
    shutil.rmtree(d, ignore_errors=True)
    with open(os.path.join(DATA, "trace.json"), "w") as f:
        json.dump({"t_start": t0, "window_s": t_end - t0, "steps": STEPS, "sleep_s": SLEEP_S,
                   "spans": exporter.spans, "device_kind": jax.devices()[0].device_kind}, f)
    print(f"trace of {t_end - t0:.6f}s written to {DATA}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
