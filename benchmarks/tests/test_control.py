#!/usr/bin/env python3
"""The serving check's control: the program computed in the nearest
precision below the one its configuration states has to come out as not
correct, read along the prompt and the tokens of the sound run.

As a test (CPU, toy size): the float32 rehearsal engine passes its 1e-3 and
the same engine in bfloat16 fails it.

On the chip, at a cell's own size, by hand (PERF.md has the readings):

    python3 benchmarks/tests/test_control.py --workload <cell> --seeds a b c

For each seed it prints the sound program's reading (max |err| / max |logit|
over the check's five positions, the number held to ``TOL_LOGITS_REL``) and
the readings of the program's own lower-precision paths on the same weights,
prompt and tokens: ``kv_cache_dtype: int8`` and ``weight_dtype: int8``; and
the reading of a fault the check is there to catch: the sound program with
its prompt's first chunk never written to the cache (a prefix taken for
cached that is not). The device's peak memory is printed after each phase
of the first seed, so that what the reference adds to the program's own
shows.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, run as harness  # noqa: E402
from benchmarks.runners import serve as runner  # noqa: E402


def make_ctx(cell_name: str, seed: int, rehearse: bool, over=None) -> dict:
    """What ``run.py`` hands a runner, for a cell of BENCHMARK.json."""
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = harness.find_cell(manifest, cell_name)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = harness.load_json(os.path.join(ROOT, entry["file"]))
    traffic = harness.load_json(os.path.join(
        harness.HERE, "traffic", cell["traffic"] + ".json"))
    if rehearse:
        config = harness.merged(config, config.get("rehearsal", {}))
        traffic = harness.merged(traffic, traffic.get("rehearsal", {}))
    config = dict(config, **(over or {}))
    os.makedirs(harness.SCRATCH, exist_ok=True)
    return {"t0": time.perf_counter(), "scratch": harness.SCRATCH,
            "cell": cell, "config": config, "traffic": traffic,
            "model": common.model_section(config),
            "reference": common.load_reference(config), "seed": seed,
            "seed31": seed % harness.SEED_MOD, "rehearse": rehearse,
            "log": harness.log}


def worst(rows) -> float:
    return max(err / scale for _, err, scale, _, _ in rows)


def sound_reading(ctx, peak=lambda: None) -> dict:
    """The runner's own check, in parts: engine, weights, prompt, the tokens
    it chose, the reference's logits along them, the rows compared; and
    what ``peak()`` read after the weights, the program and the reference."""
    cfg, engine, params, _ = runner.build_engine(ctx)
    rng = np.random.default_rng(ctx["seed31"])
    prompt = runner.check_prompt(ctx, cfg.model.vocab_size, rng)
    peaks = [peak()]
    seq, got = runner.program_logits(engine, params, prompt)
    peaks.append(peak())
    want = runner.reference_logits(ctx, params, seq, len(prompt))
    peaks.append(peak())
    tol = runner.TOL_LOGITS_REL[ctx["config"].get("torch_dtype", "bfloat16")]
    ok, rows = runner.compare_logits(got, want, tol)
    return {"params": params, "prompt": prompt, "seq": seq, "want": want,
            "tol": tol, "ok": ok, "rows": rows, "peaks": peaks}


def control_reading(sound: dict, engine, params) -> tuple:
    """(ok, rows) of another engine along the sound run's tokens."""
    _, got = runner.program_logits(engine, params, sound["prompt"],
                                   follow=sound["seq"][len(sound["prompt"]):])
    return runner.compare_logits(got, sound["want"], sound["tol"])


def test_the_float32_rehearsal_passes_and_bfloat16_in_its_place_fails():
    cell = "mistral-7b-v0.3-l16.serve-longdoc"
    sound = sound_reading(make_ctx(cell, 3000000001, True))
    assert sound["ok"], sound["rows"]
    # float32's nearest precision below: the same program in bfloat16
    low = make_ctx(cell, 3000000001, True, {"torch_dtype": "bfloat16"})
    _, engine, params, _ = runner.build_engine(low)
    ok, rows = control_reading(sound, engine, params)
    assert not ok, rows
    assert worst(rows) > 3 * sound["tol"] > 3 * worst(sound["rows"])


def test_a_run_whose_prefill_is_broken_underneath_is_not_correct(monkeypatch):
    """The rest of a run (engine, check, server, warm-up, lead-in, window,
    record) with the timed path broken where it produces its first token:
    every request still ends as asked, and ``correct`` comes out false."""
    from picotron_tpu.inference import InferenceEngine

    real = InferenceEngine.prefill

    def broken(self, params, prompt, *args, **kwargs):
        kv, last = real(self, params, prompt, *args, **kwargs)
        return kv, last[:, ::-1]  # each logit under another token's id

    monkeypatch.setattr(InferenceEngine, "prefill", broken)
    ctx = make_ctx("mistral-7b-v0.3-l16.serve-longdoc", 3000000001, True)
    ctx.update(seconds=2.0, trace=0, debug_dir=None, chips=1)
    out = runner.run(ctx)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is False
    assert any("logits" in note for note in out["notes"])


def int8_weights(params):
    """``llama.quantize_params``, one layer of one leaf at a time: its eager
    float32 copies of a whole stacked leaf do not fit beside the dense
    tree."""
    import jax.numpy as jnp

    from picotron_tpu.models import llama
    from picotron_tpu.ops.pallas.quant_matmul import quantize_weight

    def stacked(v):
        parts = [quantize_weight(v[i]) for i in range(v.shape[0])]
        return {k: jnp.stack([p[k] for p in parts]) for k in ("q", "s")}

    layers = {k: stacked(v) if k in llama.QUANT_WEIGHT_LEAVES else v
              for k, v in params["layers"].items()}
    return {**params, "layers": layers,
            "lm_head": quantize_weight(params["lm_head"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from picotron_tpu.config import Config
    from picotron_tpu.inference import InferenceEngine
    from picotron_tpu.utils import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]

    def peak() -> float:
        return (dev.memory_stats() or {}).get("peak_bytes_in_use", 0) / 1e9

    def engine_with(ctx, **inference):
        cfg = Config.from_dict(dict(runner.config_dict(ctx),
                                    inference=inference))
        return InferenceEngine(cfg, slots=ctx["config"]["serve"]["slots"],
                               max_seq_len=ctx["config"]["serve"]["max_seq_len"])

    out = []
    for seed in args.seeds:
        ctx = make_ctx(args.workload, seed, args.rehearse)
        sound = sound_reading(ctx, peak)
        rec = {"seed": seed, "prompt_len": len(sound["prompt"]),
               "tol": sound["tol"], "sound": worst(sound["rows"]),
               "sound_ok": sound["ok"],
               "peak_gb": dict(zip(("weights", "program", "reference"),
                                   sound["peaks"]))}
        params = sound.pop("params")
        engine = engine_with(ctx)
        if len(sound["prompt"]) > engine.prefill_chunk:
            whole = engine.prefill_chunked
            engine.prefill_chunked = lambda p, cache, prompt, slot: whole(
                p, cache, prompt, slot, start=engine.prefill_chunk)
            ok, rows = control_reading(sound, engine, params)
            rec.update(first_chunk_unwritten=worst(rows), fault_ok=ok)
        ok, rows = control_reading(
            sound, engine_with(ctx, kv_cache_dtype="int8"), params)
        rec.update(kv_int8=worst(rows), kv_int8_ok=ok)
        engine = engine_with(ctx, weight_dtype="int8")
        qparams = int8_weights(params)
        del params
        ok, rows = control_reading(sound, engine,
                                   engine.shard_params(qparams))
        rec.update(weights_int8=worst(rows), weights_int8_ok=ok)
        del engine, qparams
        print(json.dumps(rec), flush=True)
        out.append(rec)
    for k in ("sound", "kv_int8", "weights_int8", "first_chunk_unwritten"):
        vals = [r[k] for r in out if k in r]
        if not vals:
            continue
        print(f"{k}: smallest {min(vals):.5f} largest {max(vals):.5f} "
              f"(limit {out[0]['tol']})", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
