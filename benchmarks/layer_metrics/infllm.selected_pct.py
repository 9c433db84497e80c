"""Share of the key blocks up to a query's own that the block selection
kept: 100 x delta ``picotron_sparse_blocks_selected_total`` / delta
``picotron_sparse_blocks_visible_total`` between the window's two scrapes
(a kv head, sparse layer and live query row under the sparse rule): ``topk``
over the mean live context in blocks. A "speed-up" that stops selecting
(100) or drops the forced blocks moves it. A program without the counters
reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    visible = phases.delta(run, "picotron_sparse_blocks_visible_total")
    if visible <= 0:
        return None
    return 100.0 * phases.delta(
        run, "picotron_sparse_blocks_selected_total") / visible
