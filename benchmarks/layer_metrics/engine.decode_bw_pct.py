"""``stats.decode_bw_pct``: the decode step against the HBM roofline."""

from benchmarks import stats


def read(run):
    return stats.decode_bw_pct(run)
