"""Window seconds over tasks completed. One closed-loop client runs one task
at a time, so this is both the chip time a task costs and its latency."""


def read(run):
    return run.window_s / len(run.records) if run.records else None
