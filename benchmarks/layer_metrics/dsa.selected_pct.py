"""Share of the keys the indexer scored that the selection kept: 100 x delta
``picotron_dsa_keys_selected_total`` / delta ``picotron_dsa_keys_scored_total``
between the window's two scrapes; ``index_topk`` over the mean live context
while contexts pass it. A "speed-up" that stops selecting (100) or selects
nothing (0) moves it. A program without the counters reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    scored = phases.delta(run, "picotron_dsa_keys_scored_total")
    if scored <= 0:
        return None
    return 100.0 * phases.delta(run, "picotron_dsa_keys_selected_total") \
        / scored
