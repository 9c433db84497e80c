"""``engine.issue_enqueue_ms`` for the cell that
``serve_out_tokens_per_s`` alone bounds."""

from benchmarks import parts


def read(run):
    return parts.part_ms_a_round(run, "issue/enqueue")
