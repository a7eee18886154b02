"""XLA compiles the program counted inside the window: the sum of
``RunRecord.compiles`` (the process's compile counter's growth across
``run_task``) over the window's tasks."""


def read(run):
    counts = [getattr(r, "compiles", None) for r in run.records]
    if not counts or None in counts:
        return None
    return sum(counts)
