"""Multi-chip MFU projection for the BASELINE config ladder.

The ladder configs 3-5 (BASELINE.md:24-26) need more chips than the one
four-chip host this repo is run on, so they are *projected* from first
principles, anchored on a single-chip efficiency:

    MFU_proj = eff_1chip                      (measured compute efficiency)
             x t_compute / (t_compute + t_exposed_comm)
             x bubble_efficiency               (pipeline fill/drain)

with per-axis communication volumes computed analytically from the model
geometry (the same math the reference's NCCL schedule implies) and divided
by stated ICI bandwidth assumptions. Every assumption is a named constant
below; re-run `python tools/project_multichip.py` to regenerate
docs/PROJECTION.md's table.

Conservatism policy (each choice biases MFU_proj DOWN):
- TP/SP collectives are counted fully exposed (XLA can overlap the backward
  weight-grad matmuls with them; we take no credit).
- The DP gradient all-reduce is overlapped with the backward pass except
  for one final reduce the optimizer waits on; we charge 25% of it.
- CP ring K/V hops overlap with per-block attention compute; we charge only
  the amount by which the hop exceeds the block compute (0 in practice at
  these sizes, so the ring is charged its first hop only).
- PP p2p boundary activations are tiny but charged fully exposed.

Anchors (single-chip, an earlier builder's v5e readings on older code that no
ledger line bears out; re-anchor when a benchmark cell lands one, see
docs/PROJECTION.md status note):
- SmolLM-1.7B @ seq 2048: 55.3% MFU
- Llama-2-7B-geometry proxy @ seq 4096: 66.5% MFU
"""

from __future__ import annotations

import dataclasses

# ---- TPU v5e assumptions (public numbers; jax-ml.github.io/scaling-book) ----
PEAK_FLOPS = 1.97e14        # dense bf16 FLOPs/s/chip
ICI_BW = 4.5e10             # bytes/s one-way per link per direction
# A v5e-16 slice is a 4x4 2D torus: each mesh axis mapped onto a torus ring
# has wraparound, so ring collectives run at 2 links x ICI_BW (both
# directions). We charge the standard ring-algorithm cost:
#   all_gather / reduce_scatter of S bytes over n chips: S*(n-1)/n / (2*ICI_BW)
#   all_reduce: 2x that.
RING_BW = 2 * ICI_BW
BYTES_ACT = 2               # bf16 activations
# Default grad bytes: sync and accumulation run in fp32 (the dp/cp pmean
# and the ZeRO-1 reduce-scatter see the accumulators; downcast happens
# after sync+clip, train_step.py). Rows with grad_accum='param' override
# this to 2 B in project() — bf16 accumulators are synced as bf16.
BYTES_GRAD = 4

# single-chip compute efficiency anchors (docs/PROJECTION.md status note)
EFF_SMOLLM = 0.553
EFF_7B = 0.665


@dataclasses.dataclass
class Model:
    name: str
    L: int          # layers
    H: int          # hidden
    I: int          # intermediate (SwiGLU)
    heads: int
    kv_heads: int
    V: int          # vocab
    eff_1chip: float

    @property
    def head_dim(self):
        return self.H // self.heads

    def n_params(self) -> int:
        attn = self.H * (self.heads + 2 * self.kv_heads) * self.head_dim \
            + self.heads * self.head_dim * self.H
        mlp = 3 * self.H * self.I
        return self.L * (attn + mlp + 2 * self.H) + 2 * self.V * self.H + self.H

    def flops_per_token(self, seq: int) -> float:
        """Reference MFU numerator (utils.py:42-48): 6N + 12*L*H*S."""
        return 6 * self.n_params() + 12 * self.L * self.H * seq


SMOLLM = Model("SmolLM-1.7B", L=24, H=2048, I=8192, heads=32, kv_heads=32,
               V=49152, eff_1chip=EFF_SMOLLM)
LLAMA7B = Model("Llama-2-7B", L=32, H=4096, I=11008, heads=32, kv_heads=32,
                V=32000, eff_1chip=EFF_7B)


@dataclasses.dataclass
class Ladder:
    idx: int
    model: Model
    dp: int
    tp: int
    pp: int
    cp: int
    seq: int
    mbs: int = 1
    acc: int = 8   # microbatches per step (>= pp so 1F1B fills)
    zero1: bool = False  # dp-shard optimizer state (needed to FIT 7B on v5e)
    interleave: int = 1  # virtual pipeline stages (pp_interleave): bubble /= v
    # training.grad_accum_dtype: "float32" | "param" (bf16 accumulators)
    grad_accum: str = "float32"
    tag: str = ""  # annotation carried into the printed config column

    @property
    def chips(self):
        return self.dp * self.tp * self.pp * self.cp


def ring_ag_or_rs(bytes_full: float, n: int) -> float:
    """Seconds for a ring all-gather or reduce-scatter of a full-size
    ``bytes_full`` tensor over ``n`` chips."""
    if n == 1:
        return 0.0
    return bytes_full * (n - 1) / n / RING_BW


def ring_ar(bytes_full: float, n: int) -> float:
    return 2 * ring_ag_or_rs(bytes_full, n)


def project(lc: Ladder) -> dict:
    m, S = lc.model, lc.seq
    B = lc.mbs                       # per-microbatch batch per dp replica

    # ---- compute time per microbatch (fwd+bwd), per chip ----
    flops_mb = m.flops_per_token(S) * B * S / (lc.tp * lc.pp * lc.cp)
    t_compute = flops_mb / (PEAK_FLOPS * m.eff_1chip)

    # ---- TP/SP collectives per microbatch (Megatron, sequence-parallel) ----
    # Per layer, forward: all-gather into attn + into mlp, reduce-scatter out
    # of both; backward mirrors (the transpose collective). 4 AG + 4 RS per
    # layer per microbatch, each of the full [B, S/cp, H] activation.
    act_bytes = B * (S // lc.cp) * m.H * BYTES_ACT
    layers_here = m.L / lc.pp
    t_tp = layers_here * 8 * ring_ag_or_rs(act_bytes, lc.tp)
    # vocab-parallel CE gathers logits max/sum only (scalars per token) —
    # negligible; the fused-CE path never materializes gathered logits.

    # ---- CP ring per microbatch ----
    # K and V blocks hop cp-1 times (fwd) and kv+dkv hop cp-1 times (bwd).
    # Each hop overlaps with that block's attention compute; attention block
    # compute >> hop time at these sizes, so only the first hop is exposed.
    kv_bytes = 2 * B * (S // lc.cp) * m.kv_heads * m.head_dim * BYTES_ACT
    t_cp = (3 * kv_bytes / RING_BW) if lc.cp > 1 else 0.0  # 1 fwd + 2 bwd hops

    # ---- PP p2p per microbatch ----
    pp_bytes = B * (S // lc.cp) * m.H * BYTES_ACT / max(
        1, lc.tp)  # SP: boundary is seq-sharded over tp
    # interleaving multiplies boundary crossings by v (each microbatch
    # traverses v*pp chunks) — the cost side of the bubble credit
    t_pp = (2 * pp_bytes * lc.interleave / ICI_BW) if lc.pp > 1 else 0.0

    # ---- DP gradient sync per step (amortized over acc microbatches) ----
    bytes_grad = 2 if lc.grad_accum == "param" else BYTES_GRAD
    shard_params = m.n_params() / (lc.tp * lc.pp)
    if lc.zero1:
        # reduce-scatter the accumulator-dtype grads + all-gather the bf16
        # updated params: 6 B/param at fp32 accum vs the plain all-reduce's
        # 2 x 4 = 8 — ZeRO-1 is cheaper on the wire, not just on memory
        t_dp_full = (ring_ag_or_rs(shard_params * bytes_grad, lc.dp)
                     + ring_ag_or_rs(shard_params * 2, lc.dp))
    else:
        t_dp_full = ring_ar(shard_params * bytes_grad, lc.dp)
    t_dp = 0.25 * t_dp_full / lc.acc  # mostly overlapped with backward

    t_comm = t_tp + t_cp + t_pp + t_dp
    comm_eff = t_compute / (t_compute + t_comm)
    # interleaved 1F1B shrinks the fill/drain bubble by the virtual-stage
    # factor (parallel/pp.py::pipeline_1f1b_interleaved; equivalence-tested)
    bubble_eff = lc.acc / (lc.acc + (lc.pp - 1) / lc.interleave)

    mfu = m.eff_1chip * comm_eff * bubble_eff

    # ---- memory sanity (bytes/chip): params bf16 (2) + Adam m,v in param
    # dtype (optax zeros_like -> bf16, 4 total; NOT the fp32 8 a torch
    # fp32-state setup would need) + the grad accumulator (4 fp32 / 2
    # param). ZeRO-1 dp-shards the moments. Activations/temp buffers are
    # excluded (remat keeps them small; stated in docs/PROJECTION.md) ----
    opt_bytes = 4 / lc.dp if lc.zero1 else 4
    mem = shard_params * (2 + opt_bytes + bytes_grad)
    return dict(
        config=(f"{m.name} dp{lc.dp}/tp{lc.tp}/pp{lc.pp}/cp{lc.cp} seq{S}"
                + (" (ZeRO-1)" if lc.zero1 else "")
                + (f" [{lc.tag}]" if lc.tag else "")),
        grad_accum=lc.grad_accum,
        chips=lc.chips, mfu=100 * mfu, comm_eff=100 * comm_eff,
        bubble_eff=100 * bubble_eff,
        t_compute_ms=1e3 * t_compute, t_tp_ms=1e3 * t_tp, t_cp_ms=1e3 * t_cp,
        t_pp_ms=1e3 * t_pp, t_dp_ms=1e3 * t_dp,
        mem_gb=mem / 1e9,
    )


LADDER = [
    Ladder(3, SMOLLM, dp=2, tp=2, pp=2, cp=1, seq=2048),
    Ladder(3, SMOLLM, dp=2, tp=2, pp=2, cp=2, seq=2048),  # v5e-16 north star
    # 7B does NOT fit a 16 GB v5e at tp2/pp2 with dp-replicated grads+state
    # (1.68B params/chip x 10 B = 16.8 GB) — the GPU reference fits in 80 GB
    # H100s; on v5e the tp2/pp2 configs need our ZeRO-1 (13.5 GB), and
    # grad_accum_dtype='param' (bf16 accumulators, supported by all three
    # pipeline engines) buys another 3.4 GB of activation headroom at
    # seq 8192. The pp4/dp1 rows carry the same 16-chip 4D workload with
    # deeper model sharding instead.
    Ladder(4, LLAMA7B, dp=4, tp=2, pp=2, cp=1, seq=1024, zero1=True),
    Ladder(5, LLAMA7B, dp=2, tp=2, pp=2, cp=2, seq=8192, zero1=True,
           tag="canonical"),
    Ladder(5, LLAMA7B, dp=2, tp=2, pp=2, cp=2, seq=8192, zero1=True,
           grad_accum="param", tag="canonical + bf16 grad accum"),
    Ladder(5, LLAMA7B, dp=1, tp=2, pp=4, cp=2, seq=8192,
           tag="pp4 variant"),
    Ladder(5, LLAMA7B, dp=1, tp=2, pp=4, cp=2, seq=8192, interleave=2,
           tag="pp4 variant + pp_interleave 2"),
]


def main():
    rows = [project(lc) for lc in LADDER]
    print("| config | chips | proj MFU % | comm eff % | bubble eff % | "
          "t_comp ms | t_tp ms | t_cp ms | t_pp ms | t_dp ms | mem GB/chip |")
    print("|---|---|---|---|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['config']} | {r['chips']} | {r['mfu']:.1f} | "
              f"{r['comm_eff']:.1f} | {r['bubble_eff']:.1f} | "
              f"{r['t_compute_ms']:.2f} | {r['t_tp_ms']:.2f} | "
              f"{r['t_cp_ms']:.3f} | {r['t_pp_ms']:.3f} | "
              f"{r['t_dp_ms']:.3f} | {r['mem_gb']:.1f} |")


if __name__ == "__main__":
    main()
