"""Share of the rows the prefill programs ran that held no prompt token, for
the cells that report ``serve_tpot_mean_ms``: 100 x (1 - delta
``picotron_prefill_tokens_total`` / delta ``picotron_prefill_rows_total``)
between the window's two scrapes. A one-shot bucket pads to its power of two
and a chunk to its width, so a 38-token remainder behind the prefix store is
7 % of a 512-row chunk and 30 % of a 128-row one; every padded row is time a
prefill holds the running streams for nothing, which is
``serve_tpot_mean_ms``. A program without the rows' counter (the parent of
the PR that added it) reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    rows = phases.delta(run, "picotron_prefill_rows_total")
    if rows <= 0:
        return None
    return 100.0 * (1.0 - phases.delta(
        run, "picotron_prefill_tokens_total") / rows)
