"""The decode step of the MiMo-V2 block against the HBM roofline, memory
bound: ``opcount_mimo.decode_step_bytes`` (every weight but the embedding
table once, every held expert as run; K and V of every live token in the
full layers at 2,560 B a row; of the last ``sliding_window`` tokens of each
live slot in the sliding ones at 5,120 B) / 819 GB/s / the step's device
time (``stats.decode_runs``: the ``_decode_block_impl`` runs of the traced
stretch). The contexts are those of the requests streaming in the traced
stretch, a slot at a time (``engine.decode_bw_pct.afmoe``'s ``mean_over``).
A program without the block's counters (``picotron_swa_layer_steps_total``)
reads as nothing."""

from benchmarks import common, opcount_mimo, phases, stats

mean_over = common.load_file(
    "layer_metrics", "engine.decode_bw_pct.afmoe").mean_over


def read(run):
    got = stats.decode_runs(run)
    if got is None or "peaks" not in run or "metrics_after" not in run:
        return None
    if phases.delta(run, "picotron_swa_layer_steps_total") <= 0:
        return None
    seconds, steps = got
    trace = run["trace"]
    least = mean_over(
        run["load"]["requests"], trace["t_start"], trace["t_stop"],
        lambda ctx: opcount_mimo.decode_step_bytes(run["config"], ctx)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
