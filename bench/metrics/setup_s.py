"""Set-up seconds: process start to the window's start (device gate,
compile cache, weights, engines, prewarmed plan caches, shape warm-up)."""


def read(run):
    return run.setup_s
