"""The forward flash-attention kernel's share of its roofline, compute
bound: the causal score and value matmuls of one call, from shapes
(2 * 2*S^2*D * batch*heads / 2 FLOPs; at S 2048 the call's 75 MB of q, k, v
and o take 0.09 ms of HBM time against 0.26 ms of MXU time), over the bf16
peak, over the mean device time of the ``flash_fwd`` events of the trace
(forward and ``remat`` recompute alike: each is a whole call)."""

from benchmarks import opcount, trace_reduce


def read(run):
    trace = run.get("trace")
    if not trace or "steps" not in run or "peaks" not in run:
        return None
    calls, seconds = trace_reduce.by_base_name(trace["ops"], "flash_fwd")
    if not calls:
        return None
    t, d = run["traffic"], run["traffic"]["distributed"]
    layers = run["config"]["num_hidden_layers"]
    # FLOPs of one layer's call on this device: the micro-batch's sequences,
    # this device's heads, its share of the sequence
    per_call = (opcount.causal_attention_flops(run["config"], t["seq_length"])
                / layers * t["micro_batch_size"] / d.get("tp_size", 1)
                / d.get("cp_size", 1))
    least = per_call / run["peaks"]["bf16_flops_per_s"]
    return 100.0 * least / (seconds / calls)
