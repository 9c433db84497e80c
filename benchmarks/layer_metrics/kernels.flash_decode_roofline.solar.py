"""The flash-decode kernel's share of its roofline in the Solar Open 2 block's
two GQA layers, memory bound: K and V of the tokens cached in live slots, one
GQA layer's (``stats.live_tokens`` over the traced tail x
``opcount_solar.kv_bytes_per_token`` / GQA layers held: unpadded, and without
the part of a last block past a slot's length that the kernel does read:
the count ``engine.decode_bw_pct.solar`` uses, so the share can only read
low), over the chip's HBM bytes/s, over the mean device time of the trace's
ops whose name, the compiler's numbering and trailing underscores off, ends
in ``flash_decode_attention`` (one call a GQA layer and decode step).
``kernels.flash_decode_roofline`` divides ``opcount.kv_bytes_per_token`` by
``num_hidden_layers``, the dense block's count, and would misread a block
six of whose eight layers keep no K/V. None when no such op ran (a program
that attends densely), and for a program without the block's counters
(``picotron_kda_layer_steps_total``)."""

from benchmarks import opcount_solar, phases, stats, trace_reduce

KERNEL = "flash_decode_attention"


def read(run):
    trace = run.get("trace")
    if (not trace or "load" not in run or "peaks" not in run
            or "metrics_after" not in run):
        return None
    if phases.delta(run, "picotron_kda_layer_steps_total") <= 0:
        return None
    hits = [v for k, v in trace["ops"].items()
            if trace_reduce.base_name(k).rstrip("_").endswith(KERNEL)]
    calls = sum(v[0] for v in hits)
    if not calls:
        return None
    live = stats.live_tokens(run["load"]["requests"], trace["t_start"],
                             trace["t_stop"])
    config = run["config"]
    least = (live * opcount_solar.kv_bytes_per_token(config)
             / opcount_solar.kind_counts(config)["gqa"]
             / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (sum(v[1] for v in hits) / calls)
