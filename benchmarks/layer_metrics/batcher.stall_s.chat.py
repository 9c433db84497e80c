"""``batcher.stall_s`` for the cells that report ``serve_tpot_mean_ms``."""

from benchmarks import stalls


def read(run):
    return stalls.stall_s(run)
