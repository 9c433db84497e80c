"""The flash-decode kernel's share of its roofline in the Falcon-H1 block,
whose every layer attends, memory bound: K and V of the tokens cached in live
slots, one layer's (``stats.live_tokens`` over the traced tail x
``opcount_falcon.layer_kv_bytes_per_token``: unpadded, and without the part
of a last block past a slot's length that the kernel does read, so the share
can only read low), over the chip's HBM bytes/s, over the mean device time of
the trace's ops whose name, the compiler's numbering and trailing underscores
off, ends in ``flash_decode_attention`` (one call a layer and decode step).
``kernels.flash_decode_roofline`` counts the dense block's K/V
(``opcount.kv_bytes_per_token``) and lists the dense block's cells. None
when no such op ran (a program that attends densely), and for a program
without the block's counters (``picotron_attn_layer_steps_total``)."""

from benchmarks import opcount_falcon, phases, stats, trace_reduce

KERNEL = "flash_decode_attention"


def read(run):
    trace = run.get("trace")
    if (not trace or "load" not in run or "peaks" not in run
            or "metrics_after" not in run):
        return None
    if phases.delta(run, "picotron_attn_layer_steps_total") <= 0:
        return None
    hits = [v for k, v in trace["ops"].items()
            if trace_reduce.base_name(k).rstrip("_").endswith(KERNEL)]
    calls = sum(v[0] for v in hits)
    if not calls:
        return None
    live = stats.live_tokens(run["load"]["requests"], trace["t_start"],
                             trace["t_stop"])
    least = (live * opcount_falcon.layer_kv_bytes_per_token(run["config"])
             / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (sum(v[1] for v in hits) / calls)
