"""repro.obs — the unified tracing + metrics spine for the serving path.

Two halves, one package:

* :mod:`repro.obs.registry` — label-keyed counters/gauges/histograms with
  p50/p90/p99, lock-safe, snapshot-to-dict. The four historical telemetry
  islands (``RouterMetrics``, ``memory.CacheStats``, ``index.
  LSHTelemetry``, ``DeviceBank`` H2D counters) are views over one
  :class:`MetricsRegistry`.
* :mod:`repro.obs.spans` — structured spans (``trace_span`` context
  manager + explicit ``start_span``/``end`` for async paths) threading
  router → distributed lookup → match-pipeline stage → index backend,
  with per-request cache-attribution events
  (:mod:`repro.obs.attribution`). Exporters
  (:mod:`repro.obs.exporters`): canonical JSONL and Chrome-trace format.
* :mod:`repro.obs.compiles` — the process's XLA compile counter (one
  ``jax.monitoring`` listener), with a ``jit.compile`` span per compile.

The clock is injectable end to end: under ``repro.sim`` spans bind to the
``VirtualClock`` and the exported span stream is byte-deterministic per
seed. ``python -m repro.obs`` runs a traced quickstart of the full
serving path; ``tools/check_trace.py`` validates its artifacts.
"""

from repro.obs.attribution import (
    AttributionCollector,
    collect,
    deposit,
    tokens_saved_estimate,
)
from repro.obs.compiles import CompileCounter, compile_counter
from repro.obs.exporters import (
    InMemoryExporter,
    JsonlExporter,
    chrome_trace,
    write_chrome_trace,
)
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    latency_buckets,
    pow2_buckets,
)
from repro.obs.spans import (
    NOOP_SPAN,
    NOOP_TRACER,
    NoopTracer,
    Span,
    Tracer,
    current_span,
    get_tracer,
    set_tracer,
    trace_span,
    use_tracer,
)

__all__ = [
    "AttributionCollector",
    "CompileCounter",
    "Counter",
    "Gauge",
    "Histogram",
    "InMemoryExporter",
    "JsonlExporter",
    "MetricsRegistry",
    "NOOP_SPAN",
    "NOOP_TRACER",
    "NoopTracer",
    "Span",
    "Tracer",
    "chrome_trace",
    "collect",
    "compile_counter",
    "current_span",
    "deposit",
    "get_tracer",
    "latency_buckets",
    "pow2_buckets",
    "set_tracer",
    "tokens_saved_estimate",
    "trace_span",
    "use_tracer",
    "write_chrome_trace",
]
