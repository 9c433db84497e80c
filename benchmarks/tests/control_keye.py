#!/usr/bin/env python3
"""The serving check's readings for a cell of the Keye-VL-2.0 block, by hand
on the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_keye.py --workload <cell> --seeds a b c

For each seed it prints the sound program's reading (max |err| / max |logit|
over the check's five positions, the number held to ``TOL_LOGITS_REL``), the
device's peak memory after the weights, the program and the reference, and,
along the sound run's tokens, the readings of the program with one fault
each, which the limit has to lie under:

- ``selection_ignored``: every causal key attended (``topk`` past the
  window);
- ``topk_halved``: 1,024 keys kept where the configuration says 2,048;
- ``head_weights_left_out``: the indexer's scores summed over its heads
  unweighted (``w`` = 1);
- ``relu_left_out``: ``w . (q^I . k^I)`` without the ReLU;
- ``ki_bias_left_out``: the indexer's LayerNorm without its bias;
- ``index_rotation_left_out``: the indexer's queries and keys not rotated;
- ``qk_norm_left_out``: ``q`` and ``k`` rotated as projected, no RMSNorm a
  head;
- ``sigmoid_router``: the router's scores a sigmoid's, not a softmax's;
- ``norm_topk_prob_off``: the chosen experts' scores not renormalised;
- ``gather_off_by_one``: a decode step gathers the row after each chosen one;
- ``values_of_first_rows``: a decode step gathers the chosen keys, and the
  values of the first rows of its slot.

A fault is a wrapper around the block's own function, put in place before
the engine that runs it is built, and taken away after. The faulty engines
keep a window of ``FAULT_WINDOW`` rows a slot (the check's prompt and its
four steps fit; the sound program runs at the cell's own window): the
program is the same, and a step that attends every key then gathers 8 k rows
a slot and not 49 k.
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

FAULTS = ("selection_ignored", "topk_halved", "head_weights_left_out",
          "relu_left_out", "ki_bias_left_out", "index_rotation_left_out",
          "qk_norm_left_out", "sigmoid_router", "norm_topk_prob_off",
          "gather_off_by_one", "values_of_first_rows")
FAULT_WINDOW = 8192


@contextlib.contextmanager
def fault(name):
    """The block with one fault (None: sound), for the engines built
    inside."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.inference import kv_cache
    from picotron_tpu.models import experts, keye_vl2
    from picotron_tpu.ops import select

    kept = [(mod, n, getattr(mod, n)) for mod, n in (
        (keye_vl2, "indexer"), (keye_vl2, "attention"),
        (keye_vl2, "_angles"), (keye_vl2, "rms_norm"),
        (keye_vl2, "router_scores"), (keye_vl2, "gather_rows"),
        (keye_vl2, "_attend_rows"), (select, "index_scores"),
        (experts, "route"), (jax.nn, "relu"))]
    (indexer, attention, angles, rms_norm, _, gather_rows, _, index_scores,
     route, _) = (k[2] for k in kept)

    def topk_as(change):
        def faulty(m):
            heads, dim, topk = indexer(m)
            return heads, dim, change(topk)
        return faulty

    if name == "selection_ignored":
        keye_vl2.indexer = topk_as(lambda topk: 1 << 30)
    if name == "topk_halved":
        keye_vl2.indexer = topk_as(lambda topk: topk // 2)
    if name == "head_weights_left_out":
        select.index_scores = lambda qi, wi, *a, **kw: index_scores(
            qi, jnp.ones_like(wi), *a, **kw)
    if name == "relu_left_out":
        jax.nn.relu = lambda x: x  # the indexer's is the programs' only one
    if name == "ki_bias_left_out":
        keye_vl2.attention = lambda lp, *a, **kw: attention(
            dict(lp, ki_bias=jnp.zeros_like(lp["ki_bias"])), *a, **kw)
    if name == "index_rotation_left_out":
        def faulty(cos, sin, m):
            head, (cos_i, sin_i) = angles(cos, sin, m)
            return head, (jnp.ones_like(cos_i), jnp.zeros_like(sin_i))
        keye_vl2._angles = faulty
    if name == "qk_norm_left_out":
        # [B, S, heads, D]: the norm a head; the stream's norms are [B, S, H]
        keye_vl2.rms_norm = lambda x, w, eps: x if x.ndim == 4 \
            else rms_norm(x, w, eps)
    if name == "sigmoid_router":
        keye_vl2.router_scores = jax.nn.sigmoid
    if name == "norm_topk_prob_off":
        def faulty(scores, bias, **kw):
            chosen, _ = route(scores, bias, **kw)
            return chosen, jnp.take_along_axis(scores, chosen, axis=-1)
        experts.route = faulty
    if name == "gather_off_by_one":
        keye_vl2.gather_rows = lambda leaf, layer, rows: gather_rows(
            leaf, layer, rows + 1)
    if name == "values_of_first_rows":
        def faulty(q, src, layer, rows, count, scale):
            first = jnp.broadcast_to(jnp.arange(rows.shape[1]), rows.shape)
            nkv = src["kv"].shape[3] // 2
            return kv_cache.decode_attention(
                q, gather_rows(src["kv"], layer, rows)[:, :, :nkv],
                gather_rows(src["kv"], layer, first)[:, :, nkv:], count,
                scale)
        keye_vl2._attend_rows = faulty
    try:
        yield
    finally:
        for mod, n, was in kept:
            setattr(mod, n, was)


def fault_engine(ctx):
    """The cell's engine as the runner builds it, its window
    ``FAULT_WINDOW`` rows a slot."""
    from benchmarks.runners import serve as runner
    from picotron_tpu.config import Config
    from picotron_tpu.inference import InferenceEngine

    serve = ctx["config"]["serve"]
    return InferenceEngine(Config.from_dict(runner.config_dict(ctx)),
                           slots=serve["slots"],
                           max_seq_len=min(FAULT_WINDOW,
                                           serve["max_seq_len"]))


def reading(ctx, sound, params, name) -> tuple:
    """(worst |err| / max |logit|, ok) of the program with the fault
    ``name`` (None: sound), along the sound run's tokens."""
    from benchmarks.tests import test_control as control

    with fault(name):
        engine = fault_engine(ctx)
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
    gc.collect()
    # the sound program again at the faulty engines' window: what they are
    # read against moves with the faults alone
    rec["sound_small_window"], _ = reading(ctx, sound, params, None)
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
    for k in ["sound", "sound_small_window"] + args.faults:
        vals = [r[k] for r in out]
        print(f"{k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
              f"(limit {out[0]['tol']})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
