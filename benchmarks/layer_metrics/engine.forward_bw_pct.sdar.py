"""A forward of the SDAR-MoE block against the HBM roofline, memory bound:
``opcount_sdar.forward_bytes`` (every weight but the embedding table once,
every held expert as run; K and V of every token cached in a live slot once
a layer, whatever ``block_length``: ``stats.live_tokens`` over the traced
stretch) / 819 GB/s / the forward's device time
(``engine.forward_ms.sdar``'s ``forward_runs``). A program without the
diffusion counters reads as nothing."""

from benchmarks import common, opcount_sdar, stats

forward_runs = common.load_file(
    "layer_metrics", "engine.forward_ms.sdar").forward_runs


def read(run):
    got = forward_runs(run)
    if got is None or "peaks" not in run or "load" not in run:
        return None
    seconds, forwards = got
    trace = run["trace"]
    live = stats.live_tokens(run["load"]["requests"], trace["t_start"],
                             trace["t_stop"])
    least = opcount_sdar.forward_bytes(run["config"], live) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / forwards)
