"""``stats.itl_p99_ms`` in the open-loop cell: one decode block plus whatever
a prefill or an admission put between two blocks. Unbounded there because
two runs of one seed differ by up to 13 % (PERF.md); the stalls it counts
are part of ``serve_tpot_mean_ms``."""

from benchmarks import stats


def read(run):
    return stats.itl_p99_ms(run)
