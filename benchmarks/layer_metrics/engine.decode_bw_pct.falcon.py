"""The decode step of the Falcon-H1 block against the HBM roofline, memory
bound: ``opcount_falcon.decode_step_bytes`` (every weight but the embedding
table once, the head of the whole vocabulary among them; each live slot's
recurrent state read and written in every layer; K and V of every live token
in every layer) / 819 GB/s / the step's device time (``stats.decode_runs``:
the ``_decode_block_impl`` runs of the traced stretch). Live slots and tokens
are those of the requests streaming in the traced stretch. A program without
the block's counters (``picotron_attn_layer_steps_total``) reads as
nothing."""

from benchmarks import common, opcount_falcon, phases, stats

live_slots = common.load_file(
    "layer_metrics", "engine.decode_bw_pct.granite").live_slots


def read(run):
    got = stats.decode_runs(run)
    if got is None or "peaks" not in run or "metrics_after" not in run:
        return None
    if phases.delta(run, "picotron_attn_layer_steps_total") <= 0:
        return None
    seconds, steps = got
    trace, reqs = run["trace"], run["load"]["requests"]
    span = trace["t_start"], trace["t_stop"]
    least = opcount_falcon.decode_step_bytes(
        run["config"], live_slots(reqs, *span),
        stats.live_tokens(reqs, *span)) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
