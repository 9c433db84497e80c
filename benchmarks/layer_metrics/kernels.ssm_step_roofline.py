"""The recurrent layers' decode-step kernel's share of its roofline, memory
bound: one layer's float32 state of every live slot read once and written
once (2 x live slots x heads x d_head x d_state x 4 B: the state alone of
what ``opcount_nemotron`` / ``opcount_granite`` count a slot and layer under
``state_bytes_per_slot``, since the conv's last inputs never pass through the
kernel; a slot that is not live has no state a step must move) / the chip's
HBM bytes/s / the mean device time of the trace's ops whose name, the
compiler's numbering and trailing underscores off, ends in ``ssm_step`` (one
call a recurrent layer and decode step,
``picotron_tpu/ops/pallas/ssm_step.py``). Live slots are those of the
requests streaming in the traced tail. None when no such op ran (a program
that steps its state as the compiler's fusions, as every one before PR 57)
or the configuration holds neither block."""

from benchmarks import common, opcount_granite, opcount_nemotron, trace_reduce

KERNEL = "ssm_step"
live_slots = common.load_file(
    "layer_metrics", "engine.decode_bw_pct.granite").live_slots


def layer_state_bytes(model: dict):
    """One slot's float32 state in one recurrent layer, by the block the
    configuration's keys name; None for any other."""
    if "ssm_state_size" in model and "mamba_num_heads" in model:
        return 4 * opcount_nemotron.d_inner(model) * model["ssm_state_size"]
    if "mamba_d_state" in model and "mamba_n_heads" in model:
        return 4 * opcount_granite.d_inner(model) * model["mamba_d_state"]
    return None


def read(run):
    trace = run.get("trace")
    if not trace or "load" not in run or "peaks" not in run:
        return None
    state = layer_state_bytes(run["config"])
    hits = [v for k, v in trace["ops"].items()
            if trace_reduce.base_name(k).rstrip("_").endswith(KERNEL)]
    calls = sum(v[0] for v in hits)
    if state is None or not calls:
        return None
    slots = live_slots(run["load"]["requests"], trace["t_start"],
                       trace["t_stop"])
    least = 2 * slots * state / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(v[1] for v in hits) / calls)
