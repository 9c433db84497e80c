"""Share of the live query rows of the sparse layers that took the sparse
rule (a query at or past ``dense_len``): 100 x delta
``picotron_sparse_rows_total`` / (that + delta ``picotron_dense_rows_total``)
between the window's two scrapes. 100 in a window of long contexts; the
dense rule silently taken (every live key attended) reads 0. A program
without the counters reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    sparse = phases.delta(run, "picotron_sparse_rows_total")
    rows = sparse + phases.delta(run, "picotron_dense_rows_total")
    return 100.0 * sparse / rows if rows > 0 else None
