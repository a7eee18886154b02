"""The process's XLA compile counter.

JAX reports every backend compile, a fresh one or a retrieval from the
persistent compilation cache, as a ``jax.monitoring`` duration event
(``/jax/core/compile/backend_compile_duration``) carrying the program's
``fun_name``. :func:`compile_counter` registers one listener for it, on
its first call and only then, which per compile:

* increments ``jit.compiles`` and adds the seconds to ``jit.compile_s``
  in the counter's registry, both labelled ``program=<fun_name>``;
* where the active tracer is on the host's clock, records a
  ``jit.compile`` span (``program``) under the current span, ending now
  and lasting the compile, so idle time during a compile is put down to
  it and not to the span that asked for the program.

``PlanActAgent`` takes the counter's growth across ``run_task`` as
``RunRecord.compiles``. The listener is process-wide, as JAX's
monitoring is, so the counter counts the compiles of every thread.
"""

from __future__ import annotations

import threading
from typing import Any, Optional

from repro.obs import names as _names
from repro.obs.registry import MetricsRegistry
from repro.obs.spans import get_tracer

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Compile counts and seconds per program, and their total."""

    def __init__(self, registry: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self._lock = threading.Lock()
        self._total = 0

    @property
    def total(self) -> int:
        """Compiles seen since the counter was made."""
        with self._lock:
            return self._total

    def on_event(self, event: str, duration: float, **meta: Any) -> None:
        """The ``jax.monitoring`` duration listener."""
        if event != COMPILE_EVENT:
            return
        program = str(meta.get("fun_name", "?"))
        with self._lock:
            self._total += 1
        self.registry.counter(_names.JIT_COMPILES, program=program).inc()
        self.registry.counter(_names.JIT_COMPILE_S, program=program).inc(duration)
        tracer = get_tracer()
        if tracer.host_clock:
            tracer.record_span(_names.SPAN_JIT_COMPILE, tracer.clock() - duration,
                               program=program)


_counter: Optional[CompileCounter] = None
_counter_lock = threading.Lock()


def compile_counter() -> CompileCounter:
    """The process's counter; the first call registers its listener."""
    global _counter
    with _counter_lock:
        if _counter is None:
            import jax

            counter = CompileCounter()
            jax.monitoring.register_event_duration_secs_listener(counter.on_event)
            _counter = counter
        return _counter


__all__ = ["COMPILE_EVENT", "CompileCounter", "compile_counter"]
