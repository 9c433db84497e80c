"""``engine.issue_operands_ms`` for the cells that report
``serve_tpot_mean_ms``."""

from benchmarks import parts


def read(run):
    return parts.part_ms_a_round(run, "issue/operands")
