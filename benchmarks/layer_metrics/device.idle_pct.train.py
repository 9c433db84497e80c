"""``stats.idle_pct`` for the training cells."""

from benchmarks import stats


def read(run):
    return stats.idle_pct(run) if "steps" in run else None
