"""Mean decode step of the engine that serves the small planner and the
keyword extractor (``EngineStats.decode_s`` over its decode steps)."""


def read(run):
    e = run.engines[run.small]
    return 1000.0 * e.decode_s / e.decode_tokens if e.decode_tokens else None
