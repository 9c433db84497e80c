"""Measure the host-offload economics of this chip: link bandwidth and
the end-to-end cost of ``remat="offload"`` against ``save_attn``.

The remat="offload" mode (models/llama.py:layers_forward) parks the
decoder layer's tagged residuals in pinned host memory instead of
recomputing them — a win exactly when the host link sustains the model's
bytes-per-FLOP: ≈ (12H + 6I) bytes per token-layer against
2(4H^2 + 3HI) FLOPs (the sides meet at H ~ 14k under an assumed ~16 GB/s
PCIe against 197 TF, inversely proportional to the real bandwidth).
This tool replaces the assumption with measurements:

  1. d2h / h2d bandwidth — timed ``jax.device_put`` of a ~1 GB buffer
     between device HBM and a ``pinned_host``-memory-kind sharding;
  2. offload vs save_attn — a small-geometry train step (fits any chip)
     timed in both remat modes, same seed and batch.

Usage:
    python -m picotron_tpu.tools.measure_offload_bw [--small]

Prints a table plus one JSON line for the round record.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from picotron_tpu.config import Config


def _time(fn, *args, warmup=2, iters=10):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def measure_link_bandwidth(n_bytes: int) -> tuple[float, float]:
    """(d2h_GBps, h2d_GBps) via device_put between memory kinds."""
    dev = jax.devices()[0]
    device_s = jax.sharding.SingleDeviceSharding(dev, memory_kind="device")
    host_s = jax.sharding.SingleDeviceSharding(dev,
                                               memory_kind="pinned_host")
    x = jax.device_put(jnp.ones((n_bytes // 4,), jnp.float32), device_s)
    jax.block_until_ready(x)
    d2h = _time(lambda a: jax.device_put(a, host_s), x)
    xh = jax.device_put(x, host_s)
    jax.block_until_ready(xh)
    h2d = _time(lambda a: jax.device_put(a, device_s), xh)
    gb = n_bytes / 1e9
    return gb / d2h, gb / h2d


def _step_cfg(remat: str, small: bool) -> Config:
    if small:
        model = dict(name="tiny", num_hidden_layers=2, num_attention_heads=4,
                     num_key_value_heads=4, hidden_size=128,
                     intermediate_size=512, vocab_size=512,
                     max_position_embeddings=256, dtype="float32",
                     attention_impl="sdpa")
        seq, mbs = 128, 2
    else:
        # 7B-layer geometry, few layers: the regime the mode targets
        # (large H), sized to fit a 16 GB chip with room for host buffers
        model = dict(name="offload-probe", num_hidden_layers=4,
                     num_attention_heads=32, num_key_value_heads=32,
                     hidden_size=4096, intermediate_size=11008,
                     vocab_size=32000, max_position_embeddings=4096,
                     dtype="bfloat16")
        seq, mbs = 4096, 1
    return Config.from_dict({
        "distributed": {"dp_size": 1, "pp_size": 1, "cp_size": 1,
                        "tp_size": 1},
        "model": model,
        "training": {"seq_length": seq, "micro_batch_size": mbs,
                     "gradient_accumulation_steps": 1, "remat": remat,
                     "learning_rate": 1e-4},
        "dataset": {"name": "synthetic"},
    })


def measure_step(remat: str, small: bool) -> float:
    """Median seconds per train step at the probe geometry."""
    from picotron_tpu import train_step as ts
    from picotron_tpu.data import MicroBatchDataLoader
    from picotron_tpu.topology import topology_from_config

    cfg = _step_cfg(remat, small)
    topo = topology_from_config(cfg, devices=jax.devices()[:1])
    params, opt_state = ts.init_state(cfg, topo)
    step = ts.build_train_step(cfg, topo)
    tokens, targets = ts.shard_batch(
        next(MicroBatchDataLoader(cfg)), topo)

    # the step donates its state, so time a real carried training loop
    warmup, iters, times = 2, 5, []
    for i in range(warmup + iters):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        jax.block_until_ready(loss)
        if i >= warmup:
            times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="tiny geometry + small buffer (CPU/CI)")
    args = ap.parse_args(argv)

    n = 16 << 20 if args.small else 1 << 30
    d2h, h2d = measure_link_bandwidth(n)
    print(f"# link bandwidth ({n / 1e9:.2f} GB buffer): "
          f"d2h {d2h:.1f} GB/s, h2d {h2d:.1f} GB/s", file=sys.stderr)

    t_save = measure_step("save_attn", args.small)
    t_off = measure_step("offload", args.small)
    print(f"# step time: save_attn {t_save * 1e3:.1f} ms, "
          f"offload {t_off * 1e3:.1f} ms "
          f"(offload/save_attn = {t_off / t_save:.2f}x)", file=sys.stderr)

    print(json.dumps({
        "metric": "offload_economics",
        "value": round(t_off / t_save, 3),
        "unit": "x_step_time_vs_save_attn",
        "d2h_gbps": round(d2h, 2), "h2d_gbps": round(h2d, 2),
        "save_attn_ms": round(t_save * 1e3, 2),
        "offload_ms": round(t_off * 1e3, 2),
        "vs_baseline": 0.0}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
