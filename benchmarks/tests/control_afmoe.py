#!/usr/bin/env python3
"""The serving check's readings for a cell of the Trinity block, by hand on
the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_afmoe.py --workload <cell> --seeds a b c

For each seed it prints the sound program's reading (max |err| / max |logit|
over the check's five positions, the number held to ``TOL_LOGITS_REL``), the
device's peak memory after the weights, the program and the reference, and,
along the sound run's tokens, the readings of the program with one fault
each, which the limit has to lie under:

- ``window_ignored``: the sliding layers see every key a ring still holds
  (no window in the mask: a ring of 4,608 rows then shows 4,608 keys);
- ``ring_a_chunk_short``: a ring of ``sliding_window`` rows only, so that a
  prefill chunk's writes land on keys its first queries still see;
- ``rope_in_full_layers``: the full layers rotate q and k as the sliding
  ones do;
- ``bias_in_weights``: the router's bias joins the weights of the chosen
  experts, not the choice alone;
- ``gate_left_out``: the attention's output skips its sigmoid gate;
- ``router_bf16``: the router's scores rounded to bfloat16 before the four
  largest are taken (bfloat16 choosing in float32's place).

A fault is a wrapper around the block's own function, put in place before
the engine that runs it is built, and taken away after.
"""

import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests import test_control as control  # noqa: E402
from benchmarks.tests.control_dsv32 import bare_engine  # noqa: E402

FAULTS = ("window_ignored", "ring_a_chunk_short", "rope_in_full_layers",
          "bias_in_weights", "gate_left_out", "router_bf16")


@contextlib.contextmanager
def fault(name):
    """The block with one fault (None: sound), for the engines built
    inside."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.models import afmoe, experts

    names = ("visible", "ring_attend", "ring_rows", "ROTATED", "output_gate")
    kept = {n: getattr(afmoe, n) for n in names}
    route = experts.route

    def rounded(x):
        # an explicit op: the compiler drops a convert there and back
        # (xla_allow_excess_precision) and the fault with it
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    if name == "window_ignored":
        # no window in a chunk's mask, none in the decode step's (the
        # kernel's, or the contraction's)
        afmoe.visible = lambda pq, pk, window: kept["visible"](pq, pk, 0)
        afmoe.ring_attend = lambda q, kw, vw, pos, row, window, scale, \
            **kw_: kept["ring_attend"](q, kw, vw, pos, row, 1 << 30, scale,
                                       **kw_)
    if name == "ring_a_chunk_short":
        afmoe.ring_rows = lambda m, max_seq_len, chunk: min(
            m.sliding_window, max_seq_len)
    if name == "rope_in_full_layers":
        afmoe.ROTATED = (afmoe.WINDOW, afmoe.FULL)
    if name == "gate_left_out":
        afmoe.output_gate = lambda lp, x: 1.0
    if name == "bias_in_weights":
        experts.route = lambda scores, bias, **kw: route(
            scores + bias, jnp.zeros_like(bias), **kw)
    if name == "router_bf16":
        experts.route = lambda scores, bias, **kw: route(
            rounded(scores), bias, **kw)
    try:
        yield
    finally:
        for n in names:
            setattr(afmoe, n, kept[n])
        experts.route = route


def reading(ctx, sound, params, name) -> tuple:
    """(worst |err| / max |logit|, ok) of the program with the fault
    ``name`` (None: sound), along the sound run's tokens."""
    with fault(name):
        engine = bare_engine(ctx)
        ok, rows = control.control_reading(sound, engine, params)
    del engine
    gc.collect()
    return control.worst(rows), ok


def readings(ctx, peak) -> dict:
    sound = control.sound_reading(ctx, peak)
    params = sound.pop("params")
    rec = {"seed": ctx["seed"], "prompt_len": len(sound["prompt"]),
           "tol": sound["tol"], "sound": control.worst(sound["rows"]),
           "sound_ok": sound["ok"],
           "peak_gb": dict(zip(("weights", "program", "reference"),
                               sound["peaks"]))}
    for name in FAULTS:
        rec[name], rec[name + "_ok"] = reading(ctx, sound, params, name)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from picotron_tpu.utils import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]

    def peak() -> float:
        return (dev.memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9

    out = []
    for seed in args.seeds:
        out.append(readings(control.make_ctx(args.workload, seed,
                                             args.rehearse), peak))
        print(json.dumps(out[-1]), flush=True)
        gc.collect()
    for k in ("sound",) + FAULTS:
        vals = [r[k] for r in out]
        print(f"{k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
              f"(limit {out[0]['tol']})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
