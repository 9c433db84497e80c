"""``stats.dispatch_gap_ms`` (``picotron_dispatch_gap_seconds``, window mean)."""

from benchmarks import stats


def read(run):
    return stats.dispatch_gap_ms(run)
