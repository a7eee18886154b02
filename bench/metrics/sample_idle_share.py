"""Share of the traced window in which the device idled while the host
sampled: idle gaps the reduction puts down to ``engine.sample``."""

from bench.spans import SAMPLE, idle_share


def read(run):
    return idle_share(run.trace, SAMPLE)
