"""The backward flash-attention kernels' share of their roofline, compute
bound: the five causal matmuls of one layer's backward (s and dp rebuilt,
then dV, dK and dQ: 2.5 x the forward's two, from shapes), over the bf16
peak, over the device time one layer's backward takes: the summed time of
the backward kernels' events in the trace over the number of
``flash_bwd_dkv`` events (one a layer and step, whether dQ has a kernel of
its own or leaves with dK and dV). A backward that rebuilds the score tile twice does seven
matmuls for these five, so it reads under 5/7 of what its MXU time would
give. A kernel is an op whose name, the compiler's numbering and trailing
underscores off, ends in ``flash_bwd_dkv`` or ``flash_bwd_dq``: under the
pipeline engine's explicit ``jax.vjp`` the compiler calls them
``transpose_jvp_flash_bwd_dkv__.<n>``."""

from benchmarks import opcount, trace_reduce

KERNELS = ("flash_bwd_dkv", "flash_bwd_dq")


def _kernel(op: str) -> str | None:
    base = trace_reduce.base_name(op).rstrip("_")
    return next((k for k in KERNELS if base.endswith(k)), None)


def read(run):
    trace = run.get("trace")
    if not trace or "steps" not in run or "peaks" not in run:
        return None
    hits = [(_kernel(k), v) for k, v in trace["ops"].items() if _kernel(k)]
    calls = sum(v[0] for k, v in hits if k == "flash_bwd_dkv")
    if not calls:
        return None
    seconds = sum(v[1] for _, v in hits)
    t, d = run["traffic"], run["traffic"]["distributed"]
    layers = run["config"]["num_hidden_layers"]
    # FLOPs of one layer's backward on this device: the micro-batch's
    # sequences, this device's heads, its share of the sequence
    per_call = (2.5 * opcount.causal_attention_flops(run["config"],
                                                     t["seq_length"])
                / layers * t["micro_batch_size"] / d.get("tp_size", 1)
                / d.get("cp_size", 1))
    least = per_call / run["peaks"]["bf16_flops_per_s"]
    return 100.0 * least / (seconds / calls)
