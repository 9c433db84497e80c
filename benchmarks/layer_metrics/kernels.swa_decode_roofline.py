"""The window attend's share of its roofline, memory bound: K and V of the
last ``sliding_window`` tokens of each live slot, one sliding layer's
(``opcount_afmoe.window_attend_bytes`` over the contexts of the requests
streaming in the traced tail: what the algorithm needs, whatever implements
it, so the ring's masked rows and a last block's tail are not counted and
the share can only read low), over the chip's HBM bytes/s, over the mean
device time of the trace's ops whose name, the compiler's numbering and
trailing underscores off, ends in ``flash_decode_ring`` (one call a sliding
layer and decode step). None when no such op ran: a program that attends
the ring densely, or has no ring."""

from benchmarks import common, opcount_afmoe, trace_reduce

KERNEL = "flash_decode_ring"
mean_over = common.load_file(
    "layer_metrics", "engine.decode_bw_pct.afmoe").mean_over


def read(run):
    trace = run.get("trace")
    if not trace or "load" not in run or "peaks" not in run:
        return None
    hits = [v for k, v in trace["ops"].items()
            if trace_reduce.base_name(k).rstrip("_").endswith(KERNEL)]
    calls = sum(v[0] for v in hits)
    if not calls:
        return None
    least = mean_over(
        run["load"]["requests"], trace["t_start"], trace["t_stop"],
        lambda ctx: opcount_afmoe.window_attend_bytes(run["config"], ctx)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(v[1] for v in hits) / calls)
