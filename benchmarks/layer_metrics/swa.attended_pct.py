"""Share of the keys up to a query's own that the sliding layers attended:
100 x delta ``picotron_swa_rows_attended_total`` / delta
``picotron_swa_rows_context_total`` between the window's two scrapes (a
sliding layer and live query): ``min(context, sliding_window)`` over the
context, summed over the slots. A window that is silently not applied reads
100; with every context inside the window it reads 100 by right. A program
without the counters reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    context = phases.delta(run, "picotron_swa_rows_context_total")
    if context <= 0:
        return None
    return 100.0 * phases.delta(run, "picotron_swa_rows_attended_total") \
        / context
