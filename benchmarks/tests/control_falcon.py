#!/usr/bin/env python3
"""The serving check's readings for a cell of the Falcon-H1 block, by hand on
the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_falcon.py --workload <cell> \\
        --seeds a b c

For each seed it prints the sound program's four readings, the numbers
``runners/serve_state.py`` holds (``logits``: max |err| / max |logit| over
the check's five positions, held to ``serve.TOL_LOGITS_REL``;
``logits_rms``: rms |err| / rms |logit| over the five rows, held to
``TOL_LOGITS_RMS``; ``state``: rms |err| / rms |entry| of slot 0's state
behind the check's last decode step against the reference's float32 state,
held to ``TOL_STATE_RMS``; ``bf16_exact``: the share of those entries that
bfloat16 holds exactly, held to ``TOL_BF16_EXACT``), the device's peak memory
after the weights, the program and the reference, and, along the sound run's
tokens, the readings of the program with one fault each, which has to fail a
limit:

- ``state_dropped``: every prefill chunk starts from a zero state (a
  prompt's chunks after the first forget the state, not the conv tail);
- ``conv_tail_off_by_one``: the conv's last inputs a prefill leaves are
  those behind the last token but one;
- ``attend_one_key_short``: a decode step's attend leaves out the slot's
  newest key, the fresh row's own;
- ``key_multiplier_left_out``: ``k`` is not multiplied by ``key_multiplier``;
- ``mup_b_c_swapped``: ``ssm_multipliers``' B and C entries stand over each
  other's columns of ``in_proj``'s output;
- ``gate_norm_over_all``: the gated norm's mean square taken over all of
  ``d_ssm`` (Granite's rule), not over each group's channels;
- ``out_multipliers_exchanged``: ``ssm_out_multiplier`` on the attention's
  output and ``attention_out_multiplier`` on the mixer's;
- ``state_bf16``: the recurrent state rounded to bfloat16 wherever it is
  stored (the nearest precision below the float32 the configuration states
  for it);
- ``first_chunk_rows_stale``: the K/V rows of a prompt's first chunk are
  never written (the chunk itself attends soundly; every later chunk and
  every decode step finds zeros in the slot's first ``prefill_chunk``
  rows): an older chunk's rows, which under ``SELF_KEY``'s draw carry less
  of a softmax than a row's own key does;
- ``weights_e4m3``: every matrix of a layer rounded to E4M3 under a
  power-of-two scale a 128 x 128 block (the nearest precision below the
  bfloat16 the configuration states for them), in the program. It is read
  last: the rounded tree takes the sound one's place leaf by leaf, since
  the chip does not hold both.

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

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.runners import serve, serve_state  # noqa: E402
from benchmarks.tests import test_control as control  # noqa: E402
from benchmarks.tests.control_dsv32 import bare_engine  # noqa: E402

FAULTS = ("state_dropped", "conv_tail_off_by_one", "attend_one_key_short",
          "key_multiplier_left_out", "mup_b_c_swapped", "gate_norm_over_all",
          "out_multipliers_exchanged", "state_bf16", "first_chunk_rows_stale")
MATRICES = ("wq", "wk", "wv", "wo", "in_proj", "out_proj", "w_gate", "w_up",
            "w_down")


def other_multipliers(name, m):
    """The model section with the multipliers a fault gets wrong."""
    if name == "key_multiplier_left_out":
        return dataclasses.replace(m, key_multiplier=1.0)
    if name == "mup_b_c_swapped":
        z, x, b, c, dt = m.ssm_multipliers
        return dataclasses.replace(m, ssm_multipliers=[z, x, c, b, dt])
    if name == "out_multipliers_exchanged":
        return dataclasses.replace(
            m, ssm_out_multiplier=m.attention_out_multiplier,
            attention_out_multiplier=m.ssm_out_multiplier)
    return m


@contextlib.contextmanager
def fault(name):
    """The block with one fault (None: sound), for the engines built
    inside."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.inference import kv_cache
    from picotron_tpu.models import falcon_h1 as fh

    kept = (fh.mamba_mixer, fh.rms_norm, fh.decoder_layer, kv_cache.attend,
            fh.attention)
    mixer, norm, layer, attend, attention = kept

    def rounded(x):
        # an explicit op: the compiler drops a convert there and back
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def faulty_mixer(lp, x, conv_in, ssm_in, live, m, one_step):
        if name == "state_dropped" and not one_step:
            ssm_in = jnp.zeros_like(ssm_in)
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

    def norm_over_all(x, w, eps):
        if x.ndim == 4 and w.ndim == 2:  # the gated norm's call alone
            flat = norm(x.reshape(*x.shape[:2], -1), w.reshape(-1), eps)
            return flat.reshape(x.shape)
        return norm(x, w, eps)

    def other_layer(lp, h, cos, sin, cfg, **kw):
        cfg = dataclasses.replace(cfg, model=other_multipliers(name,
                                                               cfg.model))
        return layer(lp, h, cos, sin, cfg, **kw)

    def short_attend(q, cache, lengths, *rest, **kw):
        if q.shape[1] == 1:  # a decode step: every slot has keys before it
            lengths = jnp.maximum(lengths - 1, 1)
        return attend(q, cache, lengths, *rest, **kw)

    def first_rows_stale(lp, x, cos, sin, m, cache, pos, *rest):
        a, out = attention(lp, x, cos, sin, m, cache, pos, *rest)
        if cache is not None and "slot" in cache:  # a prefill chunk
            # the same write from a zero stream where the chunk is the
            # prompt's first: zeros are what a fresh cache holds there
            later = (pos[0] > 0).astype(x.dtype)
            _, out = attention(lp, x * later, cos, sin, m, cache, pos, *rest)
        return a, out

    if name in ("state_dropped", "conv_tail_off_by_one", "state_bf16"):
        fh.mamba_mixer = faulty_mixer
    if name == "gate_norm_over_all":
        fh.rms_norm = norm_over_all
    if name in ("key_multiplier_left_out", "mup_b_c_swapped",
                "out_multipliers_exchanged"):
        fh.decoder_layer = other_layer
    if name == "attend_one_key_short":
        kv_cache.attend = short_attend
    if name == "first_chunk_rows_stale":
        fh.attention = first_rows_stale
    try:
        yield
    finally:
        (fh.mamba_mixer, fh.rms_norm, fh.decoder_layer, kv_cache.attend,
         fh.attention) = kept


def e4m3_weights(params) -> dict:
    """``params`` with every matrix of a layer rounded to E4M3 (the DeepSeek
    reference's rounding: a power-of-two scale a 128 x 128 block), IN PLACE,
    a layer of a leaf at a time: the chip holds one tree."""
    import jax.numpy as jnp

    from benchmarks.reference.deepseek_v32 import fp8_blocks

    layers = params["layers"]
    for name in MATRICES:
        layers[name] = jnp.stack([fp8_blocks(layers[name][i])
                                  for i in range(layers[name].shape[0])])
    return params


def measure(sound: dict, got, state) -> dict:
    """What the check reads of a program's logits ``got`` and state: its
    four numbers, the state's by layer, and which limits they failed."""
    dtype = sound["dtype"]
    ok, rows = serve.compare_logits(got, sound["want"], sound["tol"])
    extra = serve_state.extra_rows(got, sound["want"], state,
                                   sound["want_state"], dtype)
    names = ("logits", "logits_rms", "state", "bf16_exact")
    oks = dict(zip(names, [bool(ok)] + [bool(r[-1]) for r in extra]))
    vals = [control.worst(rows)] + [r[1] / r[2] for r in extra]
    per_layer = [serve_state.rms_rel(g, w)
                 for g, w in zip(state, sound["want_state"])]
    return {**dict(zip(names, vals)),
            "state_by_layer": [e / sc for e, sc in per_layer],
            "ok": all(oks.values()),
            "failed": [k for k, v in oks.items() if not v]}


def reading(ctx, sound, params, name) -> dict:
    """``measure`` of the program with the fault ``name`` (None: sound),
    along the sound run's tokens."""
    with fault(name):
        engine = bare_engine(ctx)
        _, got, state = serve_state.program_logits_and_state(
            engine, params, sound["prompt"],
            follow=sound["seq"][len(sound["prompt"]):])
    del engine
    gc.collect()
    return measure(sound, got, state)


def readings(ctx, peak) -> dict:
    cfg, engine, params, _ = serve.build_engine(ctx)
    rng = np.random.default_rng(ctx["seed31"])
    prompt = serve.check_prompt(ctx, cfg.model.vocab_size, rng)
    dtype = ctx["config"].get("torch_dtype", "bfloat16")
    peaks = [peak()]
    seq, got, state = serve_state.program_logits_and_state(engine, params,
                                                           prompt)
    peaks.append(peak())
    want, want_state = serve_state.reference_logits_and_state(
        ctx, params, seq, len(prompt))
    peaks.append(peak())
    del engine
    sound = {"prompt": prompt, "seq": seq, "want": want, "dtype": dtype,
             "want_state": want_state, "tol": serve.TOL_LOGITS_REL[dtype]}
    rec = {"seed": ctx["seed"], "prompt_len": len(prompt),
           "limits": {"logits": sound["tol"],
                      "logits_rms": serve_state.TOL_LOGITS_RMS[dtype],
                      "state": serve_state.TOL_STATE_RMS[dtype],
                      "bf16_exact": serve_state.TOL_BF16_EXACT},
           "sound": measure(sound, got, state),
           "peak_gb": dict(zip(("weights", "program", "reference"), peaks))}
    for name in FAULTS:
        rec[name] = reading(ctx, sound, params, name)
    rec["weights_e4m3"] = reading(ctx, sound, e4m3_weights(params), None)
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
    for k in ("sound",) + FAULTS + ("weights_e4m3",):
        line = [f"{n} {min(r[k][n] for r in out):.5f} to "
                f"{max(r[k][n] for r in out):.5f}"
                for n in ("logits", "logits_rms", "state", "bf16_exact")]
        print(f"{k}: {'; '.join(line)}; came out correct in "
              f"{sum(r[k]['ok'] for r in out)} of {len(out)}", flush=True)
    print(f"limits: {out[0]['limits']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
