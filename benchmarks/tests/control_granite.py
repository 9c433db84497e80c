#!/usr/bin/env python3
"""The serving check's readings for a cell of the Granite-4.0-H block, by
hand on the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_granite.py --workload <cell> \\
        --seeds a b c

For each seed it prints the sound program's reading (max |err| / max |logit|
over the check's five positions, the number held to ``TOL_LOGITS_REL``), the
device's peak memory after the weights, the program and the reference, and,
along the sound run's tokens, the readings of the program with one fault
each, which the limit has to lie under:

- ``state_not_carried``: every prefill chunk starts from a zero state and
  an empty conv tail (a prompt's chunks after the first forget what came
  before them);
- ``conv_tail_off_by_one``: the conv's last inputs a prefill leaves are
  those behind the last token but one;
- ``pad_rows_advance``: the pad rows of a prompt's last chunk advance the
  state and the conv tail like real ones (the cell's check prompt is not a
  whole number of chunks, so it has some);
- ``state_bf16``: the recurrent state rounded to bfloat16 wherever it is
  stored (the nearest precision below the float32 the configuration
  states for it);
- ``router_bf16``: the router's logits rounded to bfloat16 before the ten
  largest are taken (bfloat16 choosing in float32's place).

The logits cannot tell a state stored in bfloat16 from the sound program
(every activation beside it is rounded to bfloat16 too), so each reading
comes with ``bf16_exact``: the share of the slot's state entries, after the
check's last decode step, that bfloat16 holds exactly. A float32 state
reads ~0 (one entry in 65,536 by chance), a state rounded anywhere on its
way 1: ``tests/test_granite_hybrid.py`` holds the program to that.

A fault is a wrapper around the block's own function, put in place before
the engine that runs it is built, and taken away after.
"""

import argparse
import contextlib
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.runners import serve as runner  # noqa: E402
from benchmarks.tests import test_control as control  # noqa: E402
from benchmarks.tests.control_dsv32 import bare_engine  # noqa: E402


@contextlib.contextmanager
def fault(name):
    """The block with one fault (None: sound), for the engines built
    inside."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.models import granite_hybrid as gh

    mixer, route = gh.mamba_mixer, gh.route

    def rounded(x):
        # an explicit op: the compiler drops a convert there and back
        # (xla_allow_excess_precision) and the fault with it
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def faulty_mixer(lp, x, conv_in, ssm_in, live, m, one_step):
        if name == "state_not_carried" and not one_step:
            conv_in, ssm_in = jnp.zeros_like(conv_in), jnp.zeros_like(ssm_in)
        if name == "pad_rows_advance":
            live = jnp.ones_like(live)
        if name == "state_bf16":
            ssm_in = rounded(ssm_in)
        out, conv_out, ssm_out = mixer(lp, x, conv_in, ssm_in, live, m,
                                       one_step)
        if name == "conv_tail_off_by_one" and not one_step:
            conv_out = jnp.concatenate([conv_out[:, :1], conv_out[:, :-1]],
                                       axis=1)
        if name == "state_bf16":
            ssm_out = rounded(ssm_out)
        return out, conv_out, ssm_out

    if name is not None:
        gh.mamba_mixer = faulty_mixer
    if name == "router_bf16":
        gh.route = lambda logits, k: route(rounded(logits), k)
    try:
        yield
    finally:
        gh.mamba_mixer, gh.route = mixer, route


FAULTS = ("state_not_carried", "conv_tail_off_by_one", "pad_rows_advance",
          "state_bf16", "router_bf16")


def bf16_exact_share(state) -> float:
    """Share of the entries of ``state`` (one slot's, float32) other than
    0 that bfloat16 holds exactly."""
    import jax.numpy as jnp

    state = jnp.asarray(state)
    there = state != 0
    exact = state.astype(jnp.bfloat16).astype(jnp.float32) == state
    return float(jnp.sum(exact & there) / jnp.maximum(jnp.sum(there), 1))


def reading(ctx, sound, params, name) -> tuple:
    """(worst |err| / max |logit|, ok, ``bf16_exact`` of slot 0's state) of
    the program with the fault ``name`` (None: sound), along the sound
    run's tokens."""
    with fault(name):
        engine = bare_engine(ctx)
        step, kept = engine.decode_step, {}

        def decode_step(*args):
            out = step(*args)
            kept["cache"] = out[0]
            return out

        engine.decode_step = decode_step
        ok, rows = control.control_reading(sound, engine, params)
        share = bf16_exact_share(kept["cache"]["ssm"][:, 0])
    del engine, kept
    gc.collect()
    return control.worst(rows), ok, share


def readings(ctx, peak) -> dict:
    sound = control.sound_reading(ctx, peak)
    params = sound.pop("params")
    rec = {"seed": ctx["seed"], "prompt_len": len(sound["prompt"]),
           "tol": sound["tol"], "sound": control.worst(sound["rows"]),
           "sound_ok": sound["ok"],
           "peak_gb": dict(zip(("weights", "program", "reference"),
                               sound["peaks"]))}
    _, _, rec["sound_bf16_exact"] = reading(ctx, sound, params, None)
    for name in FAULTS:
        rec[name], rec[name + "_ok"], rec[name + "_bf16_exact"] = reading(
            ctx, sound, params, name)
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
        shares = [r[k + "_bf16_exact"] for r in out]
        print(f"{k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
              f"(limit {out[0]['tol']}); bf16_exact {min(shares):.5f} to "
              f"{max(shares):.5f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
