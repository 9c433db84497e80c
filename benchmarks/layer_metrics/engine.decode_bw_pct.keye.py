"""The decode step of the Keye-VL-2.0 block against the HBM roofline, memory
bound: ``opcount_keye.decode_step_bytes`` (every weight but the embedding
table once, every held expert as run; the indexer's key of every live token
at 128 B a layer; ``min(context, topk)`` rows of K and V a slot and layer at
2,048 B) / 819 GB/s / the step's device time (``stats.decode_runs``: the
``_decode_block_impl`` runs of the traced stretch). The bytes are the
algorithm's, so a step that reads every live row of K and V under a mask
reads low. The contexts are those of the requests streaming in the traced
stretch, a slot at a time (``engine.decode_bw_pct.afmoe``'s ``mean_over``).
A program without the block's counter (``picotron_dsa_rows_attended_total``)
reads as nothing."""

from benchmarks import common, opcount_keye, phases, stats

mean_over = common.load_file(
    "layer_metrics", "engine.decode_bw_pct.afmoe").mean_over


def read(run):
    got = stats.decode_runs(run)
    if got is None or "peaks" not in run or "metrics_after" not in run:
        return None
    if phases.delta(run, "picotron_dsa_rows_attended_total") <= 0:
        return None
    seconds, steps = got
    trace = run["trace"]
    least = mean_over(
        run["load"]["requests"], trace["t_start"], trace["t_stop"],
        lambda ctx: opcount_keye.decode_step_bytes(run["config"], ctx)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
