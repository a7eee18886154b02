"""Load of the busiest routed expert: over the large-planner engine's
traced ``engine.generate`` calls, the routed (token, expert) pairs of each
MoE layer and expert (the program's ``moe.expert_tokens``, carried as the
span's ``expert_tokens``), and the largest share one expert took of its
layer's pairs. Uniform routing reads 100 / experts (1.5625% for 64).

Diagnostic at batch-1 decode: a step reads its 6 routed experts a layer
whatever the routing, and random weights make the routing an artifact.
Expert load moves ``out_tok_per_s`` once a batched step reads the union
of its rows' routed experts."""

import numpy as np


def read(run):
    t = run.trace
    if t is None:
        return None
    total = None
    for span in t.spans("engine.generate"):
        routed = span.attrs.get("expert_tokens")
        if span.attrs.get("arch") != run.big or routed is None:
            continue
        routed = np.asarray(routed, np.int64)
        total = routed if total is None else total + routed
    if total is None or total.sum() == 0:
        return None
    per_layer = total.sum(axis=1)
    live = per_layer > 0
    return float(100.0 * np.max(total[live].max(axis=1) / per_layer[live]))
