"""Tokens returned by every engine's ``generate`` calls in the window, over
the window's seconds."""


def read(run):
    return sum(len(c.out) for c in run.calls) / run.window_s
