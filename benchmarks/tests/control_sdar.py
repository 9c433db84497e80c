#!/usr/bin/env python3
"""The serving check's readings for a cell of the SDAR-MoE block, by hand on
the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_sdar.py --workload <cell> --seeds a b c

For each seed it prints the sound program's readings (``runners/
serve_blocks.py``'s numbers over the masked positions of the check's denoise
forwards, behind the whole prompt and behind its first six tokens: max |err|
/ max |logit| of the worst row, held to ``TOL_LOGITS_REL``, ``<name>_long``
the first part's alone; ``<name>_rms``, rms |err| / rms |logit| over all the
rows, held to ``TOL_RMS_REL``; and the real round's rows), the device's peak
memory after the weights,
the program and the reference, and, along the blocks the sound run held, the
readings of the program with one fault each:

- ``in_block_causal``: a block's forward attends causally (a row sees the
  rows before it and not the rest of its block);
- ``commit_left_out``: no commit forward: a block's K/V stay as its last
  denoise forward wrote them, from inputs that still held a mask;
- ``rows_left_counted``: a denoise forward's rows stay counted in
  ``lengths`` (the next forward lands behind them and sees them);
- ``remainder_dropped``: admission prefills the prompt's whole blocks and
  hands the first block nothing: the remainder's positions are fed masked;
- ``chunk_causal``: a prefill chunk attends causally;
- ``router_bf16``, ``attend_bf16``, ``norms_bf16``: bfloat16 where the
  program keeps float32: the router's logits and softmax; the attention's
  scores, softmax and weighted sum; the layers' RMSNorms (the stream's and
  the heads'). ``islands_bf16``: the three together, the program with no
  float32 left in a layer (the head's logits leave their matmul in bfloat16
  as it is).

And with the reference in the program's place, computed below float32:
``reference_bf16_matmuls`` (every matmul's operands rounded to bfloat16) and
``reference_fp8_weights`` (every matrix of a layer but the router rounded to
E4M3 under a power-of-two scale a 128 x 128 block: the nearest precision
below the bfloat16 the configuration states).

The first five and ``reference_fp8_weights`` have to fail a limit; the rest
are reported. A fault is a wrapper around the program's own function, put in
place before the engine that runs it is built, and taken away after.
``--rows <file>`` keeps every row's numbers, for choosing a limit.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import types

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.runners import serve, serve_blocks  # noqa: E402
from benchmarks.tests import test_control as control  # noqa: E402

FAULTS = ("in_block_causal", "commit_left_out", "rows_left_counted",
          "remainder_dropped", "chunk_causal", "router_bf16", "attend_bf16",
          "norms_bf16", "islands_bf16")
# the reference in the program's place: what ``forward_logits`` is handed
REFERENCES = ("reference_bf16_matmuls", "reference_fp8_weights")
MUST_FAIL = FAULTS[:5] + REFERENCES[1:]


def _causal_where(kv_cache, chunk: bool):
    """``kv_cache`` as ``models/sdar_moe.py`` sees it, its attends causal in
    a chunk (a ``slot`` entry, or no cache) or in a block's forward."""
    def attend(q, cache, *a, block=1, **kw):
        return kv_cache.attend(
            q, cache, *a, block=1 if ("slot" in cache) == chunk else block,
            **kw)

    def decode_attention(q, k, v, lengths, scale, block=1):
        return kv_cache.decode_attention(q, k, v, lengths, scale,
                                         1 if chunk else block)

    return types.SimpleNamespace(**{
        **vars(kv_cache), "attend": attend,
        "decode_attention": decode_attention})


def _attend_bf16(kv_cache):
    """``kv_cache`` as ``models/sdar_moe.py`` sees it, its attends the dense
    rule (``decode_attention``'s lines) with the scores, the softmax and the
    weighted sum in bfloat16."""
    import jax.numpy as jnp

    bf16 = jnp.bfloat16

    def decode_attention(q, k, v, lengths, scale, block=1):
        B, S, nh, D = q.shape
        T, nkv = k.shape[1], k.shape[2]
        assert k.shape[3] == D  # a head a row
        qg = q.astype(bf16).reshape(B, S, nkv, nh // nkv, D)
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, k.astype(bf16),
                            preferred_element_type=bf16) * bf16(scale)
        pos_q = lengths[:, None] - S + jnp.arange(S)[None, :]
        pos_q = jnp.minimum(pos_q // block * block + block - 1,
                            lengths[:, None] - 1)
        mask = jnp.arange(T)[None, None, :] <= pos_q[:, :, None]
        scores = jnp.where(mask[:, None, None, :, :], scores,
                           bf16(kv_cache.NEG_INF))
        p = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
        p = p / jnp.sum(p, axis=-1, keepdims=True)
        out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(bf16),
                         preferred_element_type=bf16)
        return out.reshape(B, S, nh, D).astype(q.dtype)

    def attend(q, cache, lengths, scale, layer, impl="dense", block=1):
        return decode_attention(
            q, kv_cache.layer_block(cache, "k", layer),
            kv_cache.layer_block(cache, "v", layer), lengths, scale, block)

    return types.SimpleNamespace(**{
        **vars(kv_cache), "attend": attend,
        "decode_attention": decode_attention})


def _norm_bf16(x, weight, eps):
    """``ops.rmsnorm.rms_norm`` with its mean and its root in bfloat16."""
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + jnp.asarray(eps, x.dtype)) * weight


@contextlib.contextmanager
def fault(name):
    """The program with one fault (None: sound), for the engines built
    inside."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.inference.batcher import ContinuousBatcher
    from picotron_tpu.inference.engine import InferenceEngine
    from picotron_tpu.models import sdar_moe

    kept = [(mod, n, getattr(mod, n)) for mod, n in (
        (sdar_moe, "kv_cache"), (sdar_moe, "router_scores"),
        (sdar_moe, "rms_norm"),
        (InferenceEngine, "_commit"), (InferenceEngine, "_block_forward"),
        (ContinuousBatcher, "_admit"))]
    kv_cache, _, _, commit, forward, admit = (k[2] for k in kept)

    if name in ("in_block_causal", "chunk_causal"):
        sdar_moe.kv_cache = _causal_where(kv_cache, name == "chunk_causal")
    if name == "commit_left_out":
        def no_commit(self, params, cache, x, active):
            pos = cache["lengths"]
            stats = jnp.zeros((self.cfg.model.num_hidden_layers,
                               self._n_stats), jnp.int32)
            return {**cache, "lengths": jnp.where(
                active, pos + x.shape[1], pos)}, stats
        InferenceEngine._commit = no_commit
    if name == "rows_left_counted":
        def counted(self, params, cache, x, active, head=True):
            cache, logits, stats = forward(self, params, cache, x, active,
                                           head)
            if head:  # a denoise forward: its rows stay behind the length
                cache = {**cache, "lengths": jnp.where(
                    active, cache["lengths"] + x.shape[1],
                    cache["lengths"])}
            return cache, logits, stats
        InferenceEngine._block_forward = counted
    if name == "remainder_dropped":
        def dropped(self):
            admit(self)
            self._given_n[:] = 0
        ContinuousBatcher._admit = dropped
    if name in ("router_bf16", "islands_bf16"):
        sdar_moe.router_scores = lambda logits: jax.nn.softmax(
            logits.astype(jnp.bfloat16), axis=-1).astype(jnp.float32)
    if name in ("attend_bf16", "islands_bf16"):
        sdar_moe.kv_cache = _attend_bf16(kv_cache)
    if name in ("norms_bf16", "islands_bf16"):
        sdar_moe.rms_norm = _norm_bf16
    try:
        yield
    finally:
        for mod, n, v in kept:
            setattr(mod, n, v)


def bare_engine(ctx):
    """The cell's engine, as the runner builds it, without drawing a second
    set of weights."""
    from picotron_tpu.config import Config
    from picotron_tpu.inference import InferenceEngine

    shape = ctx["config"]["serve"]
    return InferenceEngine(Config.from_dict(serve.config_dict(ctx)),
                           slots=shape["slots"],
                           max_seq_len=shape["max_seq_len"])


def rows_of(parts) -> list:
    return [r for forwards in parts for _, masked, logits in forwards
            for r in logits[masked]]


def fp8_layers(params) -> dict:
    """The tree with every matrix of a layer but the router rounded to E4M3
    (the DeepSeek reference's rounding: a power-of-two scale a 128 x 128
    block)."""
    from benchmarks.reference.deepseek_v32 import fp8_blocks

    return {**params, "layers": {
        n: fp8_blocks(v) if v.ndim >= 3 and n != "router" else v
        for n, v in params["layers"].items()}}


def measure(got, want, tol: float, tol_rms: float, n_long: int) -> dict:
    """What the check reads of ``got`` against ``want``: the worst row's max
    |err| / max |logit| (of the first ``n_long`` rows alone beside it), rms
    |err| / rms |logit| over all the rows, whether both limits held, and
    every row's own numbers."""
    ok, rows = serve.compare_logits(got, want, tol)
    err, scale = serve_blocks.rms_rel(got, want)
    per_row = [serve_blocks.rms_rel(g, w) for g, w in zip(got, want)]
    return {"max": control.worst(rows), "long": control.worst(rows[:n_long]),
            "rms": err / scale, "ok": bool(ok and err <= tol_rms * scale),
            "ok_max": bool(ok), "ok_rms": bool(err <= tol_rms * scale),
            "rows": [(r[1], r[2], e, sc) for r, (e, sc) in zip(rows,
                                                               per_row)]}


def readings(ctx, peak, faults=FAULTS, references=REFERENCES) -> dict:
    cfg, engine, params, _ = serve.build_engine(ctx)
    rng = np.random.default_rng(ctx["seed31"])
    prompt = serve.check_prompt(ctx, cfg.model.vocab_size, rng)
    dtype = ctx["config"].get("torch_dtype", "bfloat16")
    tol, tol_rms = serve.TOL_LOGITS_REL[dtype], serve_blocks.TOL_RMS_REL[dtype]
    peaks = [peak()]
    streams = serve_blocks.round_streams(engine, params, prompt)
    sound = serve_blocks.check_forwards(engine, params, prompt,
                                        ctx["reference"],
                                        stream=streams[0][3])
    forwards = sum(sound, [])
    peaks.append(peak())
    want = serve_blocks.reference_rows(ctx, params, forwards)
    peaks.append(peak())
    n_long = len(rows_of(sound[:1]))
    read = lambda got: measure(got, want, tol, tol_rms, n_long)
    rec = {"seed": ctx["seed"], "prompt_len": len(prompt), "tol": tol,
           "tol_rms": tol_rms, "forwards": len(forwards), "rows": len(want),
           "sound": read(rows_of(sound)),
           "peak_gb": dict(zip(("weights", "program", "reference"), peaks))}
    rec["round"] = [(r[0], r[1], r[2], bool(r[4]))
                    for r in serve_blocks.round_rows(
                        prompt, sound[0], streams,
                        serve_blocks.ROUND_MARGIN[dtype])]
    rec["streams"] = streams
    del engine
    for name in faults:
        gc.collect()
        with fault(name):
            faulty = bare_engine(ctx)
            got = serve_blocks.check_forwards(
                faulty, params, prompt, ctx["reference"], follow=sound)
        rec[name] = read(rows_of(got))
        del faulty, got
    gc.collect()
    lower = {"reference_bf16_matmuls": lambda: (
                 params, dict(ctx["config"], _precision="bfloat16")),
             "reference_fp8_weights": lambda: (fp8_layers(params),
                                               ctx["config"])}
    for name in references:
        tree, config = lower[name]()
        rec[name] = read(serve_blocks.reference_rows(
            dict(ctx, config=config), tree, forwards))
        del tree
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--rows", help="a file for every row's numbers")
    ap.add_argument("--sound-only", action="store_true",
                    help="the sound program's readings and no control's")
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
    names = () if args.sound_only else FAULTS + REFERENCES
    for seed in args.seeds:
        out.append(readings(
            control.make_ctx(args.workload, seed, args.rehearse), peak,
            *((), ()) if args.sound_only else (FAULTS, REFERENCES)))
        if args.rows:
            with open(args.rows, "a") as f:
                f.write(json.dumps(out[-1]) + "\n")
        print(json.dumps({k: ({n: x for n, x in v.items() if n != "rows"}
                              if isinstance(v, dict) and "rows" in v else v)
                          for k, v in out[-1].items()}), flush=True)
        gc.collect()
    for k in ("sound",) + names:
        vals = {n: [r[k][n] for r in out] for n in ("max", "long", "rms")}
        print(f"{k}: worst row {min(vals['max']):.5f} to "
              f"{max(vals['max']):.5f} (limit {out[0]['tol']}; behind the "
              f"whole prompt alone {min(vals['long']):.5f} to "
              f"{max(vals['long']):.5f}), rms {min(vals['rms']):.5f} to "
              f"{max(vals['rms']):.5f} (limit {out[0]['tol_rms']}); passed "
              f"the worst row's limit in {sum(r[k]['ok_max'] for r in out)}, "
              f"the rms's in {sum(r[k]['ok_rms'] for r in out)} of "
              f"{len(out)}", flush=True)
    gaps = [r["round"][0][1] / r["round"][0][2] for r in out]
    print(f"round: every stream held in "
          f"{sum(all(c[3] for c in r['round']) for r in out)} of {len(out)}; "
          f"the draw furthest under its forward's largest logit "
          f"{min(gaps):.5f} to {max(gaps):.5f} of the row's max |logit|",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
