"""Process start to the first instant of the window: backend, weights made
on the device from the seed, the correctness check, the cell's shapes
warmed (compiled, or loaded from the cache)."""


def read(run):
    return run["setup_s"]
