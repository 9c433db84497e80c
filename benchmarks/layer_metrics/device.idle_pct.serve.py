"""``stats.idle_pct`` for the serving cells whose tail is ``serve_itl_p99_ms``."""

from benchmarks import stats


def read(run):
    return stats.idle_pct(run) if "load" in run else None
