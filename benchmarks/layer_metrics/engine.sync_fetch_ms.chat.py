"""``engine.sync_fetch_ms`` for the cells that report
``serve_tpot_mean_ms``."""

from benchmarks import parts


def read(run):
    return parts.part_ms_a_round(run, "sync/fetch")
