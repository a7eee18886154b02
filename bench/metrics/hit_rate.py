"""Share of the window's tasks that the plan cache served (``RunRecord.hit``)."""


def read(run):
    return 100.0 * sum(r.hit for r in run.records) / len(run.records) if run.records else None
