"""Client side: the 99th percentile of the gap between consecutive streamed
tokens of one request, over every token that arrived inside the window. p99
on purpose: the server delivers ``decode_block_len`` tokens together, so
most gaps are ~0."""

from benchmarks import stats


def read(run):
    return stats.itl_p99_ms(run)
