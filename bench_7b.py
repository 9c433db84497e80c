"""Llama-2-7B-geometry proxy benchmark on the available chip(s).

The reference's second headline is 38% MFU training Llama-2-7B on 8xH100
(reference README.md:7; BASELINE ladder configs 4-5). A full 7B with
optimizer state does not fit one 16 GB v5e chip, so this benches a *proxy*
with the exact 7B layer geometry (hidden 4096, intermediate 11008, 32 heads,
vocab 32000, seq 4096, remat=full, fused linear+CE) at the best-throughput
(layers, micro-batch) point that fits — larger batches beat more layers for
MFU. Per-layer math, kernel shapes, and memory behavior match the real
model; MFU is computed against the proxy's own parameter count, which
*understates* the full-model MFU (the LM head is amortized over fewer
layers than the real model's 32).

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "device"}
with vs_baseline = mfu / 38. Executed results are committed in
docs/BENCH_7B.md. Like bench.py it measures in this process, at this
geometry, on the accelerator JAX finds, and fails there.
"""

from __future__ import annotations

import json
import sys

from picotron_tpu.bench_record import BENCH_METRICS

LLAMA2_7B_GEOM = dict(
    name="meta-llama/Llama-2-7b (proxy geometry)",
    num_attention_heads=32, num_key_value_heads=32, hidden_size=4096,
    intermediate_size=11008, vocab_size=32000, max_position_embeddings=4096,
    dtype="bfloat16", attention_impl="auto",
)


def proxy_cfg(layers: int, mbs: int, seq: int):
    from picotron_tpu.config import Config

    return Config.from_dict({
        "distributed": {"dp_size": 1, "pp_size": 1, "cp_size": 1, "tp_size": 1},
        "model": dict(LLAMA2_7B_GEOM, num_hidden_layers=layers),
        "training": {"seq_length": seq, "micro_batch_size": mbs,
                     "gradient_accumulation_steps": 1, "remat": "full",
                     "grad_accum_dtype": "param", "learning_rate": 3e-4},
        "dataset": {"name": "synthetic"},
    })


def weight_bytes(m, weight_dtype: str = "bf16") -> int:
    """Serving-weight bytes of a model config: every matmul weight at the
    storage format (bf16 = 2 bytes/element; int8 = 1 byte + one fp32
    scale per output channel — ops/pallas/quant_matmul.py), embeddings
    and norms always full precision. Pure arithmetic, mirroring
    llama.param_bytes over the tree checkpoint.load_* builds."""
    get = (m.__getitem__ if isinstance(m, dict)
           else lambda k: getattr(m, k))  # dict geometry or ModelConfig
    H, I, V, L = (get("hidden_size"), get("intermediate_size"),
                  get("vocab_size"), get("num_hidden_layers"))
    D = H // get("num_attention_heads")
    Hq = get("num_attention_heads") * D
    Hkv = get("num_key_value_heads") * D
    # (in, out) shapes of the quantizable matmuls, per layer + the head
    mats = [(H, Hq), (H, Hkv), (H, Hkv), (Hq, H),
            (H, I), (H, I), (I, H)]
    per_layer_mat = sum(i * o for i, o in mats)
    per_layer_scales = sum(o for _, o in mats)
    fp = 2  # bf16 bytes/element
    full = (V * H + H) * fp + L * 2 * H * fp  # embed + final norm + norms
    head = (H * V, V)
    if weight_dtype == "int8":
        return (full + L * (per_layer_mat + 4 * per_layer_scales)
                + head[0] + 4 * head[1])
    return full + fp * (L * per_layer_mat + head[0])


def serve_fit_report(hbm_bytes: int = 16 << 30, seq: int = 4096) -> dict:
    """The memory-headroom story int8 weights exist for: the deepest
    (layers, micro_batch) serving point — layers of the Llama-2-7B
    geometry, micro_batch = concurrent bf16-KV decode slots at the bench
    seq length — that fits one chip's HBM, per weight format. ESTIMATED
    from arithmetic (weights + per-slot KV bytes vs HBM), not measured —
    not yet validated by a TPU A/B. At the full
    32-layer depth, bf16 weights eat ~13.5 GB of a 16 GB v5e and strand
    a single slot; int8 (~6.8 GB) serves the SAME checkpoint with ~4x
    the decode batch — the whole point of the feature."""
    out = {}
    for wd in ("bf16", "int8"):
        for layers in (32, 24, 16, 8):
            m = dict(LLAMA2_7B_GEOM, num_hidden_layers=layers)
            D = m["hidden_size"] // m["num_attention_heads"]
            kv_slot = (2 * layers * seq
                       * m["num_key_value_heads"] * D * 2)  # bf16 K+V
            wb = weight_bytes(m, wd)
            mb = (hbm_bytes - wb) // kv_slot
            if mb >= 1:
                out[wd] = {"layers": layers, "micro_batch": int(mb),
                           "weight_bytes_total": wb}
                break
    return out


def main():
    from bench import run_descending, try_flash_layout_ab
    from picotron_tpu.models import llama
    from picotron_tpu.utils import (device_record, enable_compile_cache,
                                    get_mfu, peak_flops_per_chip,
                                    require_accelerator)

    require_accelerator("bench_7b.py")  # no chip, no CPU pin: no measurement
    enable_compile_cache()
    # (layers, mbs) candidates: larger batches beat more layers for MFU
    # (measured on the v5e: 6 layers @ mbs4 = 66.7% vs 8 @ mbs2 = 62.6%),
    # and fewer layers *understate* full-model MFU (the LM head amortizes
    # over fewer layers), so preferring the batch is the conservative
    # choice. Ordered best-expected-MFU first; memory-infeasible entries
    # fall through via run_descending.
    run_kw = dict(calls=4, warmup=1, steps_per_call=8)
    cfg, tok_s = run_descending(
        ((8, 4), (6, 4), (8, 2), (6, 2), (8, 1), (6, 1), (4, 1)),
        lambda lm: proxy_cfg(lm[0], lm[1], 4096),
        tag="bench_7b", **run_kw)
    # identical timing kwargs keep the layout A/B apples-to-apples
    cfg, tok_s = try_flash_layout_ab(cfg, tok_s, **run_kw)

    m = cfg.model
    n_params = llama.num_params(m)
    peak = peak_flops_per_chip()  # None on a pinned CPU: no MFU to report
    # the memory-headroom fields int8 weights exist for (ROADMAP item 3):
    # the measured geometry's weight bytes in both storage formats, and
    # the estimated deepest (layers, micro_batch) serving point per
    # format — int8 must come in at <= 55% of bf16 (tests/test_bench.py)
    weights = {"weight_dtype": "bf16",
               "weight_bytes_total": weight_bytes(m, "bf16"),
               "weight_bytes_total_int8": weight_bytes(m, "int8"),
               "serve_fit": serve_fit_report()}
    mfu = get_mfu(tok_s, n_params, m.num_hidden_layers, m.hidden_size,
                  cfg.training.seq_length, peak)
    print(json.dumps({"metric": BENCH_METRICS["bench_7b"],
                      "value": None if mfu is None else round(mfu, 2),
                      "unit": "%",
                      "vs_baseline": None if mfu is None
                      else round(mfu / 38.0, 3),
                      "tokens_per_sec_per_chip": round(tok_s, 1),
                      "device": device_record(), **weights}))
    print(f"# layers={m.num_hidden_layers} mbs={cfg.training.micro_batch_size} "
          f"seq={cfg.training.seq_length} flash={m.flash_layout} "
          f"tokens/s/chip={tok_s:.0f} params={n_params/1e9:.2f}B "
          f"peak={'n/a' if peak is None else f'{peak/1e12:.0f}TF'}",
          file=sys.stderr)


if __name__ == "__main__":
    main()
