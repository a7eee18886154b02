"""Mean ``engine.sample`` span in the traced ``generate`` calls of the
engine that serves the small planner and the keyword extractor."""

from bench.spans import mean_sample_ms


def read(run):
    return mean_sample_ms(run.trace, run.small)
