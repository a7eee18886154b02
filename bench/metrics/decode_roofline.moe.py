"""Roofline share of the large-planner engine's decode step for a
DeepSeek-V2 model: the runs of its jitted decode program
(``jit_decode_fn``) that ``decode_roofline.big`` ties to the big model's
traced ``generate`` calls, with the least time the chip could take computed
by ``bench/flops_moe.py`` (the larger of FLOPs over peak and bytes over HBM
bandwidth: every matrix outside the routed experts, the step's top-k routed
experts, the router, the latent cache rows read and written), over the
device time the trace shows, summed over the runs."""

from bench import flops_moe

PROGRAM = "jit_decode_fn"


def read(run):
    t = run.trace
    if t is None:
        return None
    m = run.model(run.big)
    if "kv_lora_rank" not in m:
        return None
    pk = run.peaks
    least = spent = 0.0
    for call in t.spans("bench.generate"):
        if call.attrs.get("arch") != run.big:
            continue
        S = int(call.attrs["S"])
        for j, x in enumerate(t.executions_in(call, PROGRAM)):
            keys = S + j + 1
            least += max(flops_moe.decode_flops(m, keys) / pk["bf16_flops_per_s"],
                         flops_moe.decode_bytes(m, keys) / pk["hbm_bytes_per_s"])
            spent += x.dur / 1e9
    return 100.0 * least / spent if spent else None
