"""Mean prefill time per ``generate`` call, both engines
(``EngineStats.prefill_s`` over the window, host clock around
``block_until_ready``)."""


def read(run):
    n = len(run.calls)
    return 1000.0 * sum(e.prefill_s for e in run.engines.values()) / n if n else None
