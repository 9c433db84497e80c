"""The flash-decode kernel's share of its roofline, memory bound: K and V of
the tokens cached in live slots, one layer's (``stats.live_tokens`` over the
traced tail x ``opcount.kv_bytes_per_token`` / layers: unpadded, and without
the part of a last block past a slot's length that the kernel does read:
the count ``stats.decode_bw_pct`` uses, so the share can only read low),
over the chip's HBM bytes/s, over the mean device time of the trace's ops
whose name, the compiler's numbering and trailing underscores off, ends in
``flash_decode_attention`` (one call a layer and decode step). None when no
such op ran: a program that attends densely, by choice or after
``inference.attend_fallback`` gave the kernel up."""

from benchmarks import opcount, stats, trace_reduce

KERNEL = "flash_decode_attention"


def read(run):
    trace = run.get("trace")
    if not trace or "load" not in run or "peaks" not in run:
        return None
    hits = [v for k, v in trace["ops"].items()
            if trace_reduce.base_name(k).rstrip("_").endswith(KERNEL)]
    calls = sum(v[0] for v in hits)
    if not calls:
        return None
    live = stats.live_tokens(run["load"]["requests"], trace["t_start"],
                             trace["t_stop"])
    config = run["config"]
    least = (live * opcount.kv_bytes_per_token(config)
             / config["num_hidden_layers"] / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (sum(v[1] for v in hits) / calls)
