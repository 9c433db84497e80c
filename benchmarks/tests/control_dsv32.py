#!/usr/bin/env python3
"""The serving check's readings for a cell of the DeepSeek-V3.2 block, by
hand on the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_dsv32.py --workload <cell> --seeds a b c d \\
        [--long-seed s --long-len 18432] [--selection-seed s]

For each seed it prints the sound program's reading (max |err| / max |logit|
over the check's five positions, the number held to ``TOL_LOGITS_REL``) and,
along the sound run's tokens, the readings the limit has to lie under:

- two faults the check is there to catch: the prompt's first chunk never
  written to the cache (a prefix taken for cached that is not), and the
  selection switched off (the sound program against the reference attending
  every causal key: what a program that stops selecting would be held to);
- the nearest precision below bfloat16, which is the published model's own
  FP8: the sound program against the reference with its indexer's ``q^I`` and
  ``k^I`` rounded to E4M3 as the published indexer stores them, and against
  the reference with every layer's matrices rounded to E4M3 in blocks of
  128 x 128. The block has no lower-precision path of its own to read (int8
  weights and cache are refused by name for it), so the lower precision is
  the reference's.

``--long-seed``: the sound reading with a prompt of ``--long-len`` tokens.
``--selection-seed``: the share of selected keys on which program and
reference differ, per layer, over every query of the check's prompt: the
program's own layer functions (bf16, the whole prompt at once: a chunk
boundary changes nothing) against the reference's stable sort in float32.
"""

import argparse
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


def bare_engine(ctx):
    """The cell's engine, as the runner builds it, without drawing a second
    set of weights."""
    from picotron_tpu.config import Config
    from picotron_tpu.inference import InferenceEngine

    serve = ctx["config"]["serve"]
    return InferenceEngine(Config.from_dict(runner.config_dict(ctx)),
                           slots=serve["slots"],
                           max_seq_len=serve["max_seq_len"])


def readings(ctx) -> dict:
    import jax

    sound = control.sound_reading(ctx)
    params = sound.pop("params")
    rec = {"seed": ctx["seed"], "prompt_len": len(sound["prompt"]),
           "tol": sound["tol"], "sound": control.worst(sound["rows"]),
           "sound_ok": sound["ok"]}
    engine = bare_engine(ctx)
    follow = sound["seq"][len(sound["prompt"]):]
    _, got = runner.program_logits(engine, params, sound["prompt"],
                                   follow=follow)

    def against(**changed):
        select = changed.pop("select", True)
        want = ctx["reference"].forward_logits(
            params, np.asarray([sound["seq"]], np.int32),
            dict(ctx["config"], **changed), jax.devices()[0],
            select=select)[0][len(sound["prompt"]) - 1:]
        ok, rows = runner.compare_logits(got, want, sound["tol"])
        return control.worst(rows), ok

    rec["selection_off"], rec["selection_off_ok"] = against(select=False)
    rec["fp8_indexer"], rec["fp8_indexer_ok"] = against(_fp8_indexer=True)
    rec["fp8_weights"], rec["fp8_weights_ok"] = against(_fp8_weights=True)
    if len(sound["prompt"]) > engine.prefill_chunk:
        whole = engine.prefill_chunked
        engine.prefill_chunked = lambda p, cache, prompt, slot: whole(
            p, cache, prompt, slot, start=engine.prefill_chunk)
        ok, rows = control.control_reading(sound, engine, params)
        rec.update(first_chunk_unwritten=control.worst(rows), fault_ok=ok)
    return rec


def selection_difference(ctx) -> dict:
    """Per layer: keys selected by exactly one of program and reference, as
    a share of the keys the reference selected."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.models import deepseek_v32 as dsv

    cfg, engine, params, _ = runner.build_engine(ctx)
    ref, model = ctx["reference"], dict(ctx["config"], _keep_selected=True)
    rng = np.random.default_rng(ctx["seed31"])
    prompt = np.asarray(runner.check_prompt(ctx, cfg.model.vocab_size, rng),
                        np.int32)
    S = len(prompt)
    box = []
    real = dsv.select_keys

    def spying(scores, k):
        out = real(scores, k)
        box.append(out)
        return out

    dsv.select_keys = spying
    # the whole prompt is one block of queries here: score fewer keys at a
    # time, so that the [queries, index heads, keys] product stays small
    key_block, dsv.KEY_BLOCK = dsv.KEY_BLOCK, 256

    def one_layer(fn):
        def run(lp, h):
            h, _ = fn(lp, h, engine._cos[:S], engine._sin[:S], engine.cfg,
                      return_kv=True)
            return h, box.pop()[0]  # [S, S] bool: the keys each query chose
        return jax.jit(run)

    dev = jax.devices()[0]
    cos, sin = ref.rope_angles(S, int(model["qk_rope_head_dim"]),
                               float(model["rope_theta"]),
                               model.get("rope_scaling"))
    h_ref = params["embed"][jnp.asarray(prompt)].astype(jnp.float32)
    h = params["embed"][jnp.asarray(prompt)][None]
    out, i = [], 0
    try:
        for name, fn, count in dsv.layer_groups(cfg.model):
            run = one_layer(fn)
            for j in range(count):
                lp = jax.tree.map(lambda v: v[j], params[name])
                h, sel = run(lp, h)
                h_ref, blocks = ref.layer(lp, h_ref, cos, sin, model)
                want = np.concatenate(blocks)
                sel = np.asarray(sel)
                out.append({"layer": i,
                            "differ": float((sel != want).sum() / 2
                                            / want.sum()),
                            "selected": int(want.sum())})
                print(json.dumps(out[-1]), flush=True)
                i += 1
    finally:
        dsv.select_keys, dsv.KEY_BLOCK = real, key_block
    total = sum(r["differ"] * r["selected"] for r in out) \
        / sum(r["selected"] for r in out)
    return {"seed": ctx["seed"], "prompt_len": S, "differ": total,
            "device": str(dev)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--long-seed", type=int)
    ap.add_argument("--long-len", type=int, default=18432)
    ap.add_argument("--selection-seed", type=int)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    from picotron_tpu.utils import enable_compile_cache

    enable_compile_cache()
    out = []
    for seed in args.seeds:
        out.append(readings(control.make_ctx(args.workload, seed,
                                             args.rehearse)))
        print(json.dumps(out[-1]), flush=True)
        gc.collect()
    for k in ("sound", "selection_off", "first_chunk_unwritten",
              "fp8_indexer", "fp8_weights"):
        vals = [r[k] for r in out if k in r]
        if vals:
            print(f"{k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
                  f"(limit {out[0]['tol']})", flush=True)
    if args.long_seed is not None:
        ctx = control.make_ctx(args.workload, args.long_seed, args.rehearse)
        ctx["traffic"] = dict(ctx["traffic"],
                              check_prompt_len=args.long_len)
        sound = control.sound_reading(ctx)
        print(json.dumps({"seed": args.long_seed,
                          "prompt_len": len(sound["prompt"]),
                          "sound": control.worst(sound["rows"]),
                          "sound_ok": sound["ok"]}), flush=True)
        del sound
        gc.collect()
    if args.selection_seed is not None:
        print(json.dumps(selection_difference(control.make_ctx(
            args.workload, args.selection_seed, args.rehearse))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
