"""``stats.idle_pct`` for the serving cells that report ``serve_tpot_mean_ms``."""

from benchmarks import stats


def read(run):
    return stats.idle_pct(run) if "load" in run else None
