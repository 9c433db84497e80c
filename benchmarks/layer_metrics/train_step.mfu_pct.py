"""Model FLOP/s utilisation: the FLOPs forward and backward need per token
(6N + 12 L H S, recomputation not counted) times tokens/s/chip over the
steps' own time, over the chip's bf16 peak."""

from benchmarks import opcount


def read(run):
    if "steps" not in run or "peaks" not in run:
        return None
    busy = sum(s["t_end"] - s["t_start"] for s in run["steps"])
    rate = len(run["steps"]) * run["tokens_per_step"] / busy / run["chips"]
    flops = opcount.train_flops_per_token(run["config"], run["seq_length"])
    return 100.0 * flops * rate / run["peaks"]["bf16_flops_per_s"]
