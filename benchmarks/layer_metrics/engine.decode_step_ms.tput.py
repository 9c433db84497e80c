"""``stats.decode_step_ms`` for the saturated cells that
``serve_out_tokens_per_s`` alone bounds."""

from benchmarks import stats


def read(run):
    return stats.decode_step_ms(run)
