"""The 90th percentile of every role call's latency in the window (keyword
extraction, planning, adaptation, acting), timed around the call."""

from bench.stats import percentile


def read(run):
    return percentile([t[1] for t in run.turns], 90) if run.turns else None
