"""Roofline share of the large-planner engine's decode step: for every run
of its jitted decode program (``jit_decode_fn``) inside a traced
``generate`` call, the least time the chip could take (the larger of its
FLOPs over peak and its bytes over HBM bandwidth: the weights, the live
keys and values read and the new ones written, ``bench/flops.py``) over
the device time the trace shows, summed over the runs."""

from bench import flops

PROGRAM = "jit_decode_fn"


def read(run):
    t = run.trace
    if t is None:
        return None
    m = run.model(run.big)
    pk = run.peaks
    least = spent = 0.0
    for call in t.spans("bench.generate"):
        if call.attrs.get("arch") != run.big:
            continue
        S = int(call.attrs["S"])
        for j, x in enumerate(t.executions_in(call, PROGRAM)):
            keys = S + j + 1
            least += max(flops.decode_flops(m, keys) / pk["bf16_flops_per_s"],
                         flops.decode_bytes(m, keys) / pk["hbm_bytes_per_s"])
            spent += x.dur / 1e9
    return 100.0 * least / spent if spent else None
