"""``batcher.deliver_ms`` for the cells that report ``serve_tpot_mean_ms``."""

from benchmarks import phases


def read(run):
    return phases.phase_mean_ms(run, "step/deliver")
