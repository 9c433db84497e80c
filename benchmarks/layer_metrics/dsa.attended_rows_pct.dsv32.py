"""Latent rows the attends read as a share of the keys the indexer scored, in
the DeepSeek-V3.2 block's cell: 100 x delta
``picotron_dsa_rows_attended_total`` / delta
``picotron_dsa_keys_scored_total`` between the window's two scrapes. A decode
step that gathers the chosen rows reads ``min(context, index_topk)`` a query
and layer, so the share equals ``dsa.selected_pct`` (``index_topk`` over the
mean live context); a step that silently takes the masked walk over every
live key block reads the context, and the share is 100. A program without the
counter (the block before PR 52) reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    scored = phases.delta(run, "picotron_dsa_keys_scored_total")
    rows = phases.delta(run, "picotron_dsa_rows_attended_total")
    if scored <= 0 or rows <= 0:
        return None
    return 100.0 * rows / scored
