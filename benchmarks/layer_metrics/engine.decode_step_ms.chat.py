"""``stats.decode_step_ms`` for the cells that report ``serve_tpot_mean_ms``."""

from benchmarks import stats


def read(run):
    return stats.decode_step_ms(run)
