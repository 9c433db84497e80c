"""Measure what ``lax.cond`` stage gating actually costs on this chip.

The pipeline engines gate embedding and LM-head/loss to their owning
stage with ``lax.cond`` (models/llama.py:_stage_input/_stage_loss); the
CPU test path masks with compute-both ``jnp.where`` instead, and
docs/PP_COST.md's interleaved FLOP guardrail therefore carries the caveat
that "cond gating makes the masked work free on TPU" had never been
measured on hardware (round-3 VERDICT, weak #3). This tool measures it:
for the real SmolLM-geometry loss and embedding computations it times

  - ``cond(True)``  — the owning stage's cost,
  - ``cond(False)`` — what every OTHER stage pays under gating,
  - ``where``       — what every other stage would pay compute-both,

on a 1-device ('dp','pp','cp','tp') mesh so the exact production code
path (tp_copy / fused linear+CE / vocab-parallel embed) runs unmodified.
The predicate is a device scalar, so XLA compiles a true runtime
conditional — nothing constant-folds.

Usage:
    python -m picotron_tpu.tools.measure_cond_gating [--small]

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
from jax import lax

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.models import llama
from picotron_tpu.topology import build_topology
from picotron_tpu.utils import shard_map as shard_map_compat

P = jax.sharding.PartitionSpec


def _time(fn, *args, warmup=3, iters=20):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ts)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes (CPU smoke / CI)")
    args = ap.parse_args(argv)

    if args.small:
        m = ModelConfig(hidden_size=64, num_attention_heads=4,
                        num_key_value_heads=4, intermediate_size=128,
                        num_hidden_layers=2, vocab_size=256,
                        max_position_embeddings=128, dtype="float32")
        b, s = 2, 64
    else:
        # SmolLM-1.7B loss/embed geometry at the bench's microbatch
        m = ModelConfig(hidden_size=2048, num_attention_heads=32,
                        num_key_value_heads=32, intermediate_size=8192,
                        num_hidden_layers=2, vocab_size=49152,
                        max_position_embeddings=2048, dtype="bfloat16")
        b, s = 4, 2048
    cfg = Config(model=m)
    cfg.training.seq_length = s
    dt = jnp.dtype(m.dtype)

    topo = build_topology(1, 1, 1, 1)
    key = jax.random.PRNGKey(0)
    kh, ke, kn, kl, kt = jax.random.split(key, 5)
    h = jax.random.normal(kh, (b, s, m.hidden_size), dt)
    params = {
        "embed": jax.random.normal(ke, (m.vocab_size, m.hidden_size), dt)
        * 0.02,
        "final_norm": jnp.ones((m.hidden_size,), dt),
        "lm_head": jax.random.normal(kl, (m.hidden_size, m.vocab_size), dt)
        * 0.02,
    }
    tokens = jax.random.randint(kt, (b, s), 0, m.vocab_size)
    targets = jax.random.randint(kn, (b, s), 0, m.vocab_size)

    def loss_cond(pred, params, h, targets):
        return lax.cond(
            pred,
            lambda: llama.loss_from_hidden(params, h, targets, cfg),
            lambda: jnp.zeros((), jnp.float32))

    def loss_where(pred, params, h, targets):
        return jnp.where(pred,
                         llama.loss_from_hidden(params, h, targets, cfg),
                         0.0)

    def embed_cond(pred, params, tokens, h_recv):
        return lax.cond(
            pred,
            lambda: llama.embed_lookup(params["embed"], tokens).astype(dt),
            lambda: h_recv)

    def embed_where(pred, params, tokens, h_recv):
        emb = llama.embed_lookup(params["embed"], tokens).astype(dt)
        return jnp.where(pred, emb, h_recv)

    def shard(fn):
        return jax.jit(shard_map_compat(
            fn, mesh=topo.mesh,
            in_specs=(P(), P(), P(), P()), out_specs=P(),
            check_vma=False))

    t = jnp.array(True)
    f = jnp.array(False)
    rows = {}
    for name, fn, extra in [
        ("loss", shard(loss_cond), (params, h, targets)),
        ("loss_where", shard(loss_where), (params, h, targets)),
        ("embed", shard(embed_cond), (params, tokens, h)),
        ("embed_where", shard(embed_where), (params, tokens, h)),
    ]:
        rows[name + "_true"] = _time(fn, t, *extra)
        rows[name + "_false"] = _time(fn, f, *extra)

    plat = jax.devices()[0].platform
    print(f"# cond-gating cost, platform={plat} b={b} s={s} "
          f"hidden={m.hidden_size} vocab={m.vocab_size} dtype={m.dtype}")
    print(f"{'path':<24}{'pred=True ms':>14}{'pred=False ms':>15}")
    for k in ("loss", "loss_where", "embed", "embed_where"):
        print(f"{k:<24}{rows[k + '_true']:>14.3f}{rows[k + '_false']:>15.3f}")
    # The claim under test: cond(False) << where(False) (the compute-both
    # cost every non-owning stage would pay without gating).
    summary = {
        "platform": plat,
        "loss_owner_ms": round(rows["loss_true"], 3),
        "loss_gated_other_ms": round(rows["loss_false"], 3),
        "loss_maskedboth_other_ms": round(rows["loss_where_false"], 3),
        "embed_owner_ms": round(rows["embed_true"], 3),
        "embed_gated_other_ms": round(rows["embed_false"], 3),
        "embed_maskedboth_other_ms": round(rows["embed_where_false"], 3),
    }
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
