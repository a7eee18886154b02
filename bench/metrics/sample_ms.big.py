"""Mean ``engine.sample`` span (logits ready to the next token on the host:
the logits' copy to the host, ``sample_token``, the PRNG split, the EOS
mask) in the traced ``generate`` calls of the engine that serves the large
planner and the actor."""

from bench.spans import mean_sample_ms


def read(run):
    return mean_sample_ms(run.trace, run.big)
