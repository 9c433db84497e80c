#!/usr/bin/env python3
"""The serving check's readings for a cell of the MiMo-V2 block, by hand on
the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_mimo.py --workload <cell> --seeds a b c

For each seed it prints the sound program's reading (max |err| / max |logit|
over the check's five positions, the number held to ``TOL_LOGITS_REL``), the
device's peak memory after the weights, the program and the reference, and,
along the sound run's tokens, the readings of the program with one fault
each, which the limit has to lie under:

- ``sink_left_out``: the sliding layers' softmax without its sink;
- ``sink_in_full_layers``: the full layers given a sink too (log of the
  window, every head);
- ``window_ignored``: the sliding layers see every key a ring still holds
  (no window in the mask: a ring of 640 rows then shows 640 keys);
- ``ring_a_window_short``: a ring of ``prefill_chunk`` rows only (512: one
  window short), so that a prefill chunk's writes land on keys its first
  queries still see;
- ``whole_head_rotated``: RoPE on all 192 dimensions of a head, not 64;
- ``full_base_in_sliding``: the sliding layers rotate under ``rope_theta``;
- ``value_scale_left_out``: ``v`` without its 0.707;
- ``swa_heads_grouped_as_full``: the sliding layers' query heads 16 to a K/V
  head (K/V heads 0-3 read, 4-7 never), as the full layers group theirs;
- ``bias_in_weights``: the router's bias joins the weights of the chosen
  experts, not the choice alone.

A fault is a wrapper around the block's own function, put in place before
the engine that runs it is built, and taken away after.
"""

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

FAULTS = ("sink_left_out", "sink_in_full_layers", "window_ignored",
          "ring_a_window_short", "whole_head_rotated", "full_base_in_sliding",
          "value_scale_left_out", "swa_heads_grouped_as_full",
          "bias_in_weights")


@contextlib.contextmanager
def fault(name):
    """The block with one fault (None: sound), for the engines built
    inside."""
    import jax.numpy as jnp

    from picotron_tpu.models import afmoe, experts, mimo_v2

    kept = [(mod, n, getattr(mod, n)) for mod, n in (
        (mimo_v2, "attention"), (mimo_v2, "decode_attend"),
        (mimo_v2, "rotated_dims"), (mimo_v2, "_own_tables"),
        (afmoe, "visible"), (afmoe, "ring_rows"), (experts, "route"))]
    attention, decode_attend, _, own_tables, visible, _, route = (
        k[2] for k in kept)

    def attention_with(change):
        """``attention`` on leaves and a config that ``change(lp, cfg,
        window)`` hands back."""
        def faulty(lp, x, cos, sin, cfg, cache, pos, row, live, window,
                   return_kv):
            lp, cfg = change(lp, cfg, window)
            return attention(lp, x, cos, sin, cfg, cache, pos, row, live,
                             window, return_kv)
        return faulty

    def regrouped(w, nkv: int):
        # K/V head j's columns are head j // 2's: 16 query heads a head
        cols = w.reshape(w.shape[0], nkv, -1)
        return cols[:, jnp.arange(nkv) // 2].reshape(w.shape)

    if name == "sink_left_out":
        mimo_v2.attention = attention_with(lambda lp, cfg, window: (
            {n: v for n, v in lp.items() if n != "sink"}, cfg))
    if name == "sink_in_full_layers":
        mimo_v2.attention = attention_with(lambda lp, cfg, window: (
            lp if window else dict(lp, sink=jnp.full(
                (cfg.model.num_attention_heads,),
                math.log(cfg.model.sliding_window), jnp.float32)), cfg))
    if name == "window_ignored":
        # no window in a chunk's mask, none in the decode step's (the
        # kernel's, or the contraction's)
        afmoe.visible = lambda pq, pk, window: visible(pq, pk, 0)
        mimo_v2.decode_attend = lambda q, k, v, pos, row, window, *a, **kw: \
            decode_attend(q, k, v, pos, row, window and 1 << 30, *a, **kw)
    if name == "ring_a_window_short":
        afmoe.ring_rows = lambda m, max_seq_len, chunk: min(chunk,
                                                            max_seq_len)
    if name == "whole_head_rotated":
        mimo_v2.rotated_dims = lambda m: m.head_dim
    if name == "full_base_in_sliding":
        mimo_v2._own_tables = lambda cos, sin, window: own_tables(cos, sin,
                                                                  False)
    if name == "value_scale_left_out":
        mimo_v2.attention = attention_with(lambda lp, cfg, window: (
            lp, dataclasses.replace(cfg, model=dataclasses.replace(
                cfg.model, attention_value_scale=1.0))))
    if name == "swa_heads_grouped_as_full":
        mimo_v2.attention = attention_with(lambda lp, cfg, window: (
            dict(lp, **{n: regrouped(lp[n], cfg.model.swa_num_key_value_heads)
                        for n in ("wk", "wv")}) if window else lp, cfg))
    if name == "bias_in_weights":
        experts.route = lambda scores, bias, **kw: route(
            scores + bias, jnp.zeros_like(bias), **kw)
    try:
        yield
    finally:
        for mod, n, was in kept:
            setattr(mod, n, was)


def reading(ctx, sound, params, name) -> tuple:
    """(worst |err| / max |logit|, ok) of the program with the fault
    ``name`` (None: sound), along the sound run's tokens."""
    from benchmarks.tests import test_control as control
    from benchmarks.tests.control_dsv32 import bare_engine

    with fault(name):
        engine = bare_engine(ctx)
        ok, rows = control.control_reading(sound, engine, params)
    del engine
    gc.collect()
    return control.worst(rows), ok


def readings(ctx, peak, faults) -> dict:
    from benchmarks.tests import test_control as control

    sound = control.sound_reading(ctx, peak)
    params = sound.pop("params")
    rec = {"seed": ctx["seed"], "prompt_len": len(sound["prompt"]),
           "tol": sound["tol"], "sound": control.worst(sound["rows"]),
           "sound_ok": sound["ok"],
           "peak_gb": dict(zip(("weights", "program", "reference"),
                               sound["peaks"]))}
    for name in faults:
        rec[name], rec[name + "_ok"] = reading(ctx, sound, params, name)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=list(FAULTS),
                    choices=FAULTS)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from benchmarks.tests import test_control as control
    from picotron_tpu.utils import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]

    def peak() -> float:
        return (dev.memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9

    out = []
    for seed in args.seeds:
        out.append(readings(control.make_ctx(args.workload, seed,
                                             args.rehearse), peak,
                            args.faults))
        print(json.dumps(out[-1]), flush=True)
        gc.collect()
    for k in ["sound"] + args.faults:
        vals = [r[k] for r in out]
        print(f"{k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
              f"(limit {out[0]['tol']})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
