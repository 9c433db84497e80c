"""The delta rule's decode-step kernel's share of its roofline, memory bound:
one KDA layer's float32 state of every live slot read once and written once
(2 x live slots x ``opcount_solar.layer_state_bytes``, heads x keys x values
x 4 B: the state alone of what ``opcount_solar.state_bytes_per_slot`` counts
a slot and layer, since the convs' last inputs never pass through the
kernel; a slot that is not live has no state a step must move) / the chip's
HBM bytes/s / the mean device time of the trace's ops whose name, the
compiler's numbering and trailing underscores off, ends in ``kda_step`` (one
call a KDA layer and decode step, ``picotron_tpu/ops/pallas/kda_step.py``).
Live slots are those of the requests streaming in the traced tail. None when
no such op ran (a program that steps its state as the compiler's fusions, as
every one before PR 59) or the configuration has no ``linear_attn_config``."""

from benchmarks import common, opcount_solar, trace_reduce

KERNEL = "kda_step"
live_slots = common.load_file(
    "layer_metrics", "engine.decode_bw_pct.granite").live_slots


def read(run):
    trace = run.get("trace")
    if not trace or "load" not in run or "peaks" not in run:
        return None
    if "linear_attn_config" not in run.get("config", {}):
        return None
    hits = [v for k, v in trace["ops"].items()
            if trace_reduce.base_name(k).rstrip("_").endswith(KERNEL)]
    calls = sum(v[0] for v in hits)
    if not calls:
        return None
    slots = live_slots(run["load"]["requests"], trace["t_start"],
                       trace["t_stop"])
    least = 2 * slots * opcount_solar.layer_state_bytes(run["config"]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(v[1] for v in hits) / calls)
