"""Model FLOPs of every prefill and decode token the engines processed in
the window (``bench/flops.py``, from the configuration's widths) over the
window's seconds, less the profiler's own start and stop, times the
device's bf16 peak."""


def read(run):
    seconds = run.window_s - run.profiler_s
    return 100.0 * run.flops() / (seconds * run.peaks["bf16_flops_per_s"])
