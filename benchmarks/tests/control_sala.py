#!/usr/bin/env python3
"""The serving check's readings for a cell of the MiniCPM-SALA block, by
hand on the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_sala.py --workload <cell> --seeds a b c

For each seed it prints the sound program's reading (max |err| / max |logit|
over the check's five positions, the number held to ``TOL_LOGITS_REL``), the
device's peak memory after the weights, the program and the reference, and,
along the sound run's tokens, the readings of the program with one fault
each, which the limit has to lie under:

- ``selection_off_by_a_block``: every query keeps, before its own block, the
  blocks one past the ones it chose;
- ``forced_blocks_dropped``: block 0 and the local window compete by their
  scores like any other block;
- ``stale_seam_window``: the compressed key of the window that began in the
  chunk before is made from zeros where that chunk's last keys belong;
- ``dense_rule_past_dense_len``: a prefill row past ``dense_len`` attends
  over every block up to its own;
- ``state_not_carried``: every prefill chunk starts from a zero state;
- ``pad_rows_advance``: rows that are not live advance the state (a check
  prompt of whole chunks has none: it then reads as the sound program, and
  ``tests/test_minicpm_sala.py`` holds the pad rows);
- ``lambda_one``: the state never decays (``slope = 0``);
- ``no_rope``: the lightning layers' q and k are not rotated;
- ``state_bf16``: the lightning state rounded to bfloat16 wherever it is
  stored (the nearest precision below the float32 the configuration
  states for it).

The logits cannot tell a state stored in bfloat16 from the sound program
(the output norm and every activation beside it are rounded to bfloat16
too), so each reading comes with ``bf16_exact``: the share of the slot's
state entries, after the check's last decode step, that bfloat16 holds
exactly. A float32 state reads ~0, a state rounded anywhere on its way 1:
``tests/test_minicpm_sala.py`` holds the program to that.

A fault is a wrapper around the block's own function, put in place before
the engine that runs it is built, and taken away after.
"""

import argparse
import contextlib
import dataclasses
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
from benchmarks.tests.control_granite import bf16_exact_share  # noqa: E402


@contextlib.contextmanager
def fault(name):
    """The block with one fault (None: sound), for the engines built
    inside."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.models import minicpm_sala as sala

    real = {n: getattr(sala, n) for n in (
        "lightning_mixer", "select_blocks", "block_scores", "compress_block",
        "apply_rope")}

    def rounded(x):
        # an explicit op: the compiler drops a convert there and back
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def with_sizes(m, **sizes):
        return dataclasses.replace(
            m, sparse_config={**m.sparse_config, **sizes})

    def faulty_mixer(lp, x, cos, sin, state_in, live, m, one_step):
        if name == "state_not_carried" and not one_step:
            state_in = jnp.zeros_like(state_in)
        if name == "pad_rows_advance":
            live = jnp.ones_like(live)
        if name == "lambda_one":
            lp = {**lp, "slope": jnp.zeros_like(lp["slope"])}
        if name == "state_bf16":
            state_in = rounded(state_in)
        out, state = real["lightning_mixer"](lp, x, cos, sin, state_in, live,
                                             m, one_step)
        return out, rounded(state) if name == "state_bf16" else state

    def faulty_select(q, kc, pos_q, m):
        if name == "dense_rule_past_dense_len" and q.shape[1] > 1:
            m = with_sizes(m, dense_len=2 ** 30)
        chosen = real["select_blocks"](q, kc, pos_q, m)
        if name == "selection_off_by_a_block":
            blk = jnp.arange(chosen.shape[-1], dtype=jnp.int32)
            cur = (pos_q // m.sparse_config["block_size"])[:, :, None, None]
            chosen = (jnp.roll(chosen, 1, axis=-1).at[..., 0].set(False)
                      & (blk < cur)) | (blk == cur)
        return chosen

    if name in ("state_not_carried", "pad_rows_advance", "lambda_one",
                "state_bf16"):
        sala.lightning_mixer = faulty_mixer
    if name in ("selection_off_by_a_block", "dense_rule_past_dense_len"):
        sala.select_blocks = faulty_select
    if name == "forced_blocks_dropped":
        sala.block_scores = lambda q, kc, pos_q, m: real["block_scores"](
            q, kc, pos_q, with_sizes(m, init_blocks=0, window_size=0))
    if name == "stale_seam_window":
        sala.compress_block = lambda k, prev, st: real["compress_block"](
            k, jnp.zeros_like(prev), st)
    if name == "no_rope":
        sala.apply_rope = lambda x, cos, sin: x
    try:
        yield
    finally:
        for n, fn in real.items():
            setattr(sala, n, fn)


FAULTS = ("selection_off_by_a_block", "forced_blocks_dropped",
          "stale_seam_window", "dense_rule_past_dense_len",
          "state_not_carried", "pad_rows_advance", "lambda_one", "no_rope",
          "state_bf16")


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
        share = bf16_exact_share(kept["cache"]["state"][:, 0])
    del engine, kept
    gc.collect()
    return control.worst(rows), ok, share


def readings(ctx, peak, faults=FAULTS) -> dict:
    sound = control.sound_reading(ctx, peak)
    params = sound.pop("params")
    rec = {"seed": ctx["seed"], "prompt_len": len(sound["prompt"]),
           "tol": sound["tol"], "sound": control.worst(sound["rows"]),
           "sound_ok": sound["ok"],
           "peak_gb": dict(zip(("weights", "program", "reference"),
                               sound["peaks"]))}
    _, _, rec["sound_bf16_exact"] = reading(ctx, sound, params, None)
    for name in faults:
        rec[name], rec[name + "_ok"], rec[name + "_bf16_exact"] = reading(
            ctx, sound, params, name)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=list(FAULTS))
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
                                             args.rehearse), peak,
                            args.faults))
        print(json.dumps(out[-1]), flush=True)
        gc.collect()
    for k in ["sound"] + list(args.faults):
        vals = [r[k] for r in out]
        shares = [r[k + "_bf16_exact"] for r in out]
        print(f"{k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
              f"(limit {out[0]['tol']}); bf16_exact {min(shares):.5f} to "
              f"{max(shares):.5f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
