"""The full layers' decode attend's share of its roofline, memory bound: K
and V of every token cached in a live slot, one full layer's
(``opcount_mimo.full_attend_bytes`` over the contexts of the requests
streaming in the traced tail: 4 x 192 + 4 x 128 bfloat16 a token, what the
algorithm needs, so a last block's tail is not counted and the share can
only read low), over the chip's HBM bytes/s, over the mean device time of
the trace's ops whose name, the compiler's numbering and trailing
underscores off, ends in ``flash_decode_attention`` (one call a full layer
and decode step). None when no such op ran: a program that attends the full
layers densely."""

from benchmarks import common, opcount_mimo, trace_reduce

KERNEL = "flash_decode_attention"
mean_over = common.load_file(
    "layer_metrics", "engine.decode_bw_pct.afmoe").mean_over


def kernel_share(run, kernel: str, layer_bytes):
    """100 x the least time one call of ``kernel`` could take (``layer_bytes
    (config, contexts)`` of one layer, averaged over the traced stretch,
    over the HBM's bytes/s) / the mean device time of its calls."""
    trace = run.get("trace")
    if not trace or "load" not in run or "peaks" not in run:
        return None
    hits = [v for k, v in trace["ops"].items()
            if trace_reduce.base_name(k).rstrip("_").endswith(kernel)]
    calls = sum(v[0] for v in hits)
    if not calls:
        return None
    least = mean_over(
        run["load"]["requests"], trace["t_start"], trace["t_stop"],
        lambda ctx: layer_bytes(run["config"], ctx)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(v[1] for v in hits) / calls)


def read(run):
    return kernel_share(run, KERNEL, opcount_mimo.full_attend_bytes)
