"""Graph in host memory → ordering → ``FactorCache.factor`` → the
cell's first solve on its own path, until that answer is on the host."""


def read(run):
    return run.cold_start_s or None
