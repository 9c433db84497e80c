"""``stats.dispatch_gap_ms`` for the saturated cells that
``serve_out_tokens_per_s`` alone bounds: with admission serial it holds the
round's prefills, the time between two decode blocks that makes no token
of a running stream."""

from benchmarks import stats


def read(run):
    return stats.dispatch_gap_ms(run)
