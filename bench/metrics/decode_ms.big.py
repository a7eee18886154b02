"""Mean decode step of the engine that serves the large planner and the
actor (``EngineStats.decode_s`` over its decode steps in the window)."""


def read(run):
    e = run.engines[run.big]
    return 1000.0 * e.decode_s / e.decode_tokens if e.decode_tokens else None
