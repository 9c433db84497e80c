"""The pipelined expert pass's share of its roofline with two-matrix relu^2
experts, memory bound: one expert layer's held experts whole, the live rows'
latent in and their float32 sum out (``opcount_nemotron.pipelined_pass_bytes``
over the requests streaming in the traced tail) / the chip's HBM bytes/s /
the mean device time of the trace's ops whose name, the compiler's numbering
and trailing underscores off, ends in ``pipelined_experts`` (one call an
expert layer and decode step). None when no such op ran: a program that runs
its held experts as the loop, or has no such block."""

from benchmarks import common, opcount_nemotron, trace_reduce

KERNEL = "pipelined_experts"
live_slots = common.load_file(
    "layer_metrics", "engine.decode_bw_pct.granite").live_slots


def read(run):
    trace = run.get("trace")
    if not trace or "load" not in run or "peaks" not in run \
            or "moe_latent_size" not in run["config"]:
        return None
    hits = [v for k, v in trace["ops"].items()
            if trace_reduce.base_name(k).rstrip("_").endswith(KERNEL)]
    calls = sum(v[0] for v in hits)
    if not calls:
        return None
    rows = live_slots(run["load"]["requests"], trace["t_start"],
                      trace["t_stop"])
    least = opcount_nemotron.pipelined_pass_bytes(run["config"], rows) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(v[1] for v in hits) / calls)
