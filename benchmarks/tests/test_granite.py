"""The Granite-4.0-H configuration's pieces of the benchmark, as new cases
beside the files that exist (a PR that adds a cell edits none of them):
``opcount_granite`` against numbers worked by hand and against the program's
tree, the reference against the program's forward, the configuration and the
cell through the seams and the harness."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmarks import common, opcount_granite as og

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "granite-4.0-h-small-ep2-l10"
CELL = NAME + ".serve-chat-closed"


def config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


# ---- opcount ---------------------------------------------------------------


def test_granite_counts_by_hand():
    m = config()
    p = og.params_by_part(m)
    # in_proj 4096 x (8192 z + 8448 xBC + 128 dt) + out_proj 8192 x 4096 +
    # conv 8448 x 4 + 8448 + dt_bias, A_log, D 3 x 128 + gated norm 8192
    assert p["mamba"] == 4096 * 16768 + 8192 * 4096 + 8448 * 5 + 384 + 8192
    assert round(p["mamba"] / 1e6, 1) == 102.3
    # q and o 4096 x 4096 each, k and v 4096 x 1024 each
    assert p["attention"] == 2 * 16_777_216 + 2 * 4_194_304
    assert p["router"] == 4096 * 72  # the published width: 36 held x 2
    assert p["routed_expert"] == 4096 * 1536 + 768 * 4096 == 9_437_184
    assert p["shared_mlp"] == 4096 * 3072 + 1536 * 4096
    assert og.kind_counts(m) == (9, 1)
    assert round(og.layer_params(m, "mamba") / 1e6, 1) == 461.2
    assert round(og.layer_params(m, "attention") / 1e6, 1) == 400.9
    # 9 x 461.2 M + 400.9 M + 50,176 x 4096 (tied: once) + final norm
    assert og.num_params(m) == 4_757_211_776
    assert round(2 * og.num_params(m) / 1e9, 2) == 9.51
    # state: 9 x (128 x 64 x 128 x 4 B + 3 x 8448 x 2 B); K/V: 2 x 8 x 128 x 2 B
    assert og.state_bytes_per_slot(m) == 9 * (4_194_304 + 50_688) == 38_204_928
    assert og.kv_bytes_per_token(m) == 4096
    # 64 slots x 4096: 2.44 GB of state, 1.07 GB of K/V
    assert round(64 * og.state_bytes_per_slot(m) / 1e9, 2) == 2.45
    assert 64 * 4096 * og.kv_bytes_per_token(m) == 1_073_741_824


def test_granite_decode_bytes_and_prefill_flops():
    m = config()
    weights = 2 * og.num_params(m)
    # weights once + each live slot's state read and written + live K/V
    assert og.decode_step_bytes(m, 64, 51200) == \
        weights + 2 * 64 * 38_204_928 + 51200 * 4096
    assert round(og.decode_step_bytes(m, 64, 51200) / 1e9, 1) == 14.6
    # no slot live: the weights alone
    assert og.decode_step_bytes(m, 0, 0) == weights
    assert og.decode_step_bytes(m, 1, 100) == \
        weights + 2 * 38_204_928 + 100 * 4096
    # a chunk of 256: C B^T and the masked product under the mask, the
    # state read out and updated
    assert og.ssd_scan_flops(m, 256) == \
        256 * 256 * 128 + 256 * 256 * 8192 + 4 * 256 * 8192 * 128
    assert og.ssd_scan_flops(m, 300) == og.ssd_scan_flops(m, 256) \
        + 44 * 44 * (128 + 8192) + 4 * 44 * 8192 * 128
    # a token reaches 5 of the 36 held experts on average (10 of 72)
    per_layer = 4096 * 72 + 18_874_368 + 5 * 9_437_184
    flops = og.prefill_flops_per_token(m, 1000)
    assert flops == 2 * (9 * (og.params_by_part(m)["mamba"] + per_layer)
                         + 41_943_040 + per_layer) \
        + 9 * og.ssd_scan_flops(m, 256) / 256 + 4 * 32 * 128 * 1000
    assert round(flops / 1e9, 2) == 3.33
    # the head is a prompt's, not a token's: its last position alone
    assert og.head_flops(m) == 2 * 50176 * 4096


def test_granite_parameters_are_the_programs_tree():
    from picotron_tpu.config import ModelConfig
    from picotron_tpu.models import granite_hybrid as gh

    m = config()
    model = ModelConfig(**common.model_section(m))
    assert gh.num_params(model) == og.num_params(m) == 4_757_211_776
    cache = jax.eval_shape(lambda: gh.init_cache(
        model, m["serve"]["slots"], m["serve"]["max_seq_len"]))
    state = sum(a.size * a.dtype.itemsize
                for n, a in cache.items() if n in ("ssm", "conv"))
    assert state == 64 * og.state_bytes_per_slot(m)
    kv = cache["k"].size * 2 + cache["v"].size * 2
    assert kv == 64 * 4096 * og.kv_bytes_per_token(m)


# ---- the reference against the program's forward ---------------------------


def test_reference_matches_the_programs_prefill_and_decode():
    """As ``test_reference.py`` holds ``dense_decoder`` to
    ``llama.forward_logits``: the rehearsal's toy size in float32, a prompt
    in two chunks and four decode steps, against the reference's full
    forward (tests/test_granite_hybrid.py has the many cases)."""
    from benchmarks.tests import test_control as control
    from benchmarks.runners import serve as runner

    sound = control.sound_reading(control.make_ctx(CELL, 3000000001, True))
    assert len(sound["prompt"]) == 530 and len(sound["seq"]) == 534
    assert sound["ok"], sound["rows"]
    assert control.worst(sound["rows"]) < 1e-4
    # the reference itself: batch of two, the loss over its own logits
    ctx = control.make_ctx(CELL, 3000000001, True)
    ref, params = ctx["reference"], sound["params"]
    tokens = np.random.default_rng(0).integers(1, 512, (2, 40))
    logits = ref.forward_logits(params, tokens, ctx["config"])
    assert logits.shape == (2, 40, 512) and logits.dtype == np.float32
    targets = np.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -np.mean(np.take_along_axis(np.asarray(logp), targets[..., None], -1))
    assert ref.loss(params, tokens, targets, ctx["config"]) == \
        pytest.approx(float(ce), abs=1e-4)
    with open(ref.__file__) as f:
        assert "picotron_tpu" not in f.read().split('"""', 2)[2]
    assert runner.TOL_LOGITS_REL["bfloat16"] == 3e-2  # not this PR's to move


# ---- the seams and the harness ---------------------------------------------


def test_the_configuration_and_the_cell_load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    c = config()
    assert entry["reduced"] == c["reduced"] == [
        "num_hidden_layers", "layer_types", "num_local_experts",
        "vocab_size", "ep_size"]
    assert set(c["reduced_from"]) == set(c["reduced"])
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "chat-closed-80", 1)
    got = common.model_section(c)
    assert list(got)[:9] == list(common.MODEL_KEYS)
    assert got["model_type"] == "granitemoehybrid"
    assert got["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (got["num_local_experts"], got["ep_size"], got["ep_rank"]) == \
        (36, 2, 0)
    assert got["intermediate_size"] == 768 and got["hidden_size"] == 4096
    assert common.load_reference(c).__file__.endswith("granite_hybrid.py")
    # every cell that lists the configuration's metrics reports setup_s too
    listed = [m["name"] for m in manifest["end_to_end"]
              if CELL in m.get("workloads", ())]
    # the inter-token tail spread too widely to bound (PERF.md section 6)
    assert listed == ["serve_out_tokens_per_s"]
    assert all(m["moves"] == "serve_out_tokens_per_s"
               for m in manifest["per_layer"]
               if CELL in m.get("workloads", ()))
    with open(os.path.join(HERE, "..", "traffic", "chat-closed-80.json")) as f:
        t = json.load(f)
    assert (t["loop"], t["clients"], t["shapes"]) == ("closed", 80, 960)
    assert t["prompt_len"] == {"dist": "log_uniform", "min": 64, "max": 1024}
    assert t["documents"]["asks"] == 1  # the block plan, nothing shared
    assert t["output_len"] == {"dist": "uniform", "min": 128, "max": 384}
    # a size the window sends, and not a whole number of chunks: pad rows
    assert t["check_prompt_len"] == 1000 and t["lead_in_seconds"] == 15


def test_a_model_key_modelconfig_lacks_still_exits_2(capsys):
    c = dict(config(), mamba_d_mystery=1)
    c["model_keys"] = c["model_keys"] + ["mamba_d_mystery"]
    with pytest.raises(SystemExit) as e:
        common.model_section(c)
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "'mamba_d_mystery'" in err and "cannot express it" in err


@pytest.mark.parametrize("trace,computed", [
    (0, {"serve_out_tokens_per_s", "setup_s"}),
    (2, {"serve_out_tokens_per_s", "setup_s",
         "ssm.state_updates_per_step", "ssm.prefill_scan_tokens_per_s",
         "moe.held_assignments_per_step.granite",
         "engine.prefill_tflops.granite", "batcher.dispatch_gap_ms.tput",
         "batcher.deliver_ms.tput"}),
])
def test_rehearsal_runs_the_cell_end_to_end(trace, computed):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "2", "--trace", str(trace),
         "--rehearse"], cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["compiles_in_window"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert computed == set(out["computed"])
