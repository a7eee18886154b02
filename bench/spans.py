"""Readings of the program's own spans in a traced run.

The reduction (``bench/trace.py``) places the spans the program exported
(``repro.obs``) on the profiler's clock beside the harness's ``bench.*``
annotations. A program that records no ``engine.decode`` span, as before
its engine was instrumented, gives no reading: each function here returns
None for it.
"""

from __future__ import annotations

from typing import Optional

DECODE = "engine.decode"
SAMPLE = "engine.sample"


def instrumented(trace) -> bool:
    """The traced program records its engine's per-step spans."""
    return trace is not None and any(h.name == DECODE for h in trace.host)


def mean_sample_ms(trace, arch: str) -> Optional[float]:
    """Mean ``engine.sample`` span, in ms, of the traced ``bench.generate``
    calls of ``arch`` (a span counts where its middle lies in the call);
    0 where the program records decode steps but no sampling span."""
    if not instrumented(trace):
        return None
    calls = [(c.start, c.end) for c in trace.spans("bench.generate")
             if c.attrs.get("arch") == arch]
    spent = []
    for s in trace.spans(SAMPLE):
        mid = (s.start + s.end) / 2
        if any(a <= mid <= b for a, b in calls):
            spent.append(s.end - s.start)
    return sum(spent) / len(spent) / 1e6 if spent else 0.0


def idle_share(trace, label: str) -> Optional[float]:
    """Idle put down to ``label`` over the traced window, in %."""
    if not instrumented(trace) or trace.n_devices == 0:
        return None
    return 100.0 * trace.gap_seconds.get(label, 0.0) / trace.window_s
