"""``stats.decode_bw_pct`` for the cells that report ``serve_tpot_mean_ms``."""

from benchmarks import stats


def read(run):
    return stats.decode_bw_pct(run)
