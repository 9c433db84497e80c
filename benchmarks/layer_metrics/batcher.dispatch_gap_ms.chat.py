"""``stats.dispatch_gap_ms`` for the cells that report ``serve_tpot_mean_ms``."""

from benchmarks import stats


def read(run):
    return stats.dispatch_gap_ms(run)
