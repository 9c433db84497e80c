"""``stats.idle_pct`` for the saturated cells that ``serve_out_tokens_per_s``
alone bounds (their inter-token tail spreads too widely to list): every
second the device waits is a second no token is made."""

from benchmarks import stats


def read(run):
    return stats.idle_pct(run) if "load" in run else None
