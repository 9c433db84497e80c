"""``stats.decode_step_ms``: device time of one decode step, from the trace."""

from benchmarks import stats


def read(run):
    return stats.decode_step_ms(run)
