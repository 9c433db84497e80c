"""The MiniCPM-SALA configuration's pieces of the benchmark, as new cases
beside the files that exist (a PR that adds a cell edits none of them):
``opcount_sala`` against numbers worked by hand and against the program's
tree, the reference against the program's forward, the readers on the
counters a run scrapes, the configuration and the cell through the seams
and the harness."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmarks import common, opcount_sala as osl

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "minicpm-sala-l12"
CELL = NAME + ".serve-longctx-decode"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


# ---- opcount ---------------------------------------------------------------


def test_sala_counts_by_hand():
    m = config()
    p = osl.params_by_part(m)
    # q, k, v, o and the gate 4096 x 4096 each; q and k norms 128 each, the
    # output norm 4096, 32 slopes
    assert p["lightning"] == 5 * 16_777_216 + 256 + 4096 + 32
    # q, o and the gate 4096 x 4096, k and v 4096 x 256
    assert p["sparse"] == 3 * 16_777_216 + 2 * 1_048_576
    assert p["mlp"] == 3 * 4096 * 16384 == 201_326_592
    assert p["embed"] == p["head"] == 73448 * 4096
    assert osl.kind_counts(m) == (9, 3)
    assert round(osl.layer_params(m, "lightning") / 1e6, 1) == 285.2
    assert round(osl.layer_params(m, "sparse") / 1e6, 1) == 253.8
    # 9 x 285.2 M + 3 x 253.8 M + 2 x 300.8 M
    assert osl.num_params(m) == 3_930_008_096
    assert round(2 * osl.num_params(m) / 1e9, 2) == 7.86
    # state: 9 x 32 x 128 x 128 x 4 B; K/V: 3 x 2 (K, V) x 2 x 128 x 2 B
    assert osl.state_bytes_per_slot(m) == 9 * 2_097_152 == 18_874_368
    assert osl.kv_bytes_per_token(m) == 3 * 1024
    assert osl.compressed_bytes_per_token(m) == 3 * 512 / 16
    # 8 slots x 65,536: 1.61 GB of K/V, 0.05 of compressed keys, 0.15 of state
    assert 8 * 65536 * osl.kv_bytes_per_token(m) == 1_610_612_736
    assert round(8 * 65536 * osl.compressed_bytes_per_token(m) / 1e9, 2) \
        == 0.05
    assert round(8 * osl.state_bytes_per_slot(m) / 1e9, 2) == 0.15


def test_sala_decode_bytes():
    m = config()
    weights = 2 * (osl.num_params(m) - 73448 * 4096)
    assert round(weights / 1e9, 2) == 7.26
    # 8 slots at 45,000: the state twice, the compressed keys of the live
    # context, 4,096 kept rows a slot (not 45,000)
    got = osl.decode_step_bytes(m, 8, 8 * 45000)
    assert got == weights + 2 * 8 * 18_874_368 + 8 * 45000 * 96 \
        + 8 * 4096 * 3072
    assert round(got / 1e9, 2) == 7.70
    # every live key read instead would be 1.1 GB, not 0.10
    assert round(8 * 45000 * 3072 / 1e9, 1) == 1.1
    # a context shorter than the kept rows is read whole
    assert osl.decode_step_bytes(m, 1, 1000) == \
        weights + 2 * 18_874_368 + 1000 * 96 + 1000 * 3072
    assert osl.decode_step_bytes(m, 0, 0) == weights


def test_sala_parameters_are_the_programs_tree():
    from picotron_tpu.config import ModelConfig
    from picotron_tpu.models import minicpm_sala as sala

    m = config()
    model = ModelConfig(**common.model_section(m))
    assert sala.num_params(model) == osl.num_params(m) == 3_930_008_096
    cache = jax.eval_shape(lambda: sala.init_cache(
        model, m["serve"]["slots"], m["serve"]["max_seq_len"]))
    size = lambda n: cache[n].size * cache[n].dtype.itemsize
    assert size("state") == 8 * osl.state_bytes_per_slot(m)
    assert size("k") + size("v") == 8 * 65536 * osl.kv_bytes_per_token(m)
    assert size("kc") == 8 * 65536 * osl.compressed_bytes_per_token(m)
    resident = 2 * osl.num_params(m) + sum(size(n) for n in cache)
    assert 9.5e9 < resident < 9.7e9


# ---- the reference against the program's forward ---------------------------


def test_reference_matches_the_programs_prefill_and_decode():
    """As ``test_reference.py`` holds ``dense_decoder`` to
    ``llama.forward_logits``: the rehearsal's toy size in float32, a prompt
    in two chunks and four decode steps, against the reference's full
    forward (tests/test_minicpm_sala.py has the many cases)."""
    from benchmarks.runners import serve as runner
    from benchmarks.tests import test_control as control

    sound = control.sound_reading(control.make_ctx(CELL, 3000000001, True))
    assert len(sound["prompt"]) == 600 and len(sound["seq"]) == 604
    assert sound["ok"], sound["rows"]
    assert control.worst(sound["rows"]) < 1e-4
    # the reference itself: batch of two, the loss over its own logits
    ctx = control.make_ctx(CELL, 3000000001, True)
    ref, params = ctx["reference"], sound["params"]
    tokens = np.random.default_rng(0).integers(1, 512, (2, 90))
    logits = ref.forward_logits(params, tokens, ctx["config"])
    assert logits.shape == (2, 90, 512) and logits.dtype == np.float32
    targets = np.roll(tokens, -1, axis=1)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ce = -np.mean(np.take_along_axis(np.asarray(logp), targets[..., None], -1))
    assert ref.loss(params, tokens, targets, ctx["config"]) == \
        pytest.approx(float(ce), abs=1e-4)
    with open(ref.__file__) as f:
        assert "picotron_tpu" not in f.read().split('"""', 2)[2]
    assert runner.TOL_LOGITS_REL["bfloat16"] == 3e-2  # not this PR's to move


@pytest.mark.parametrize("name", ["state_not_carried", "lambda_one",
                                  "no_rope", "forced_blocks_dropped",
                                  "dense_rule_past_dense_len",
                                  "selection_off_by_a_block"])
def test_a_fault_fails_the_rehearsals_check(name):
    """``control_sala.py``'s faults at the rehearsal's size: each leaves the
    float32 limit far behind along the sound run's tokens."""
    from benchmarks.tests import control_sala, test_control as control

    ctx = control.make_ctx(CELL, 3000000001, True)
    sound = control.sound_reading(ctx)
    params = sound.pop("params")
    err, ok, _ = control_sala.reading(ctx, sound, params, name)
    assert not ok and err > 10 * sound["tol"], (name, err)


# ---- the readers -----------------------------------------------------------


def _scrape(**counters) -> str:
    return "\n".join(f"picotron_{k}_total {v}" for k, v in counters.items())


def test_the_readers_read_the_counters():
    names = ("infllm.selected_pct", "infllm.sparse_rows_pct",
             "lightning.state_updates_per_step")
    read = {n: common.load_file("layer_metrics", n).read for n in names}
    before = _scrape(sparse_blocks_selected=100, sparse_blocks_visible=1000,
                     sparse_rows=10, dense_rows=5,
                     lightning_state_updates=70, lightning_layer_steps=9)
    after = _scrape(sparse_blocks_selected=100 + 64 * 6,
                    sparse_blocks_visible=1000 + 704 * 6, sparse_rows=13,
                    dense_rows=6, lightning_state_updates=70 + 72,
                    lightning_layer_steps=18)
    run = {"metrics_before": before, "metrics_after": after}
    assert read["infllm.selected_pct"](run) == pytest.approx(100 * 64 / 704)
    assert read["infllm.sparse_rows_pct"](run) == pytest.approx(75.0)
    assert read["lightning.state_updates_per_step"](run) == 8.0
    # a program without the counters (the parent): nothing, and no error
    bare = {"metrics_before": "picotron_x_total 1",
            "metrics_after": "picotron_x_total 2"}
    assert [read[n](bare) for n in names] == [None, None, None]
    assert [read[n]({}) for n in names] == [None, None, None]
    bw = common.load_file("layer_metrics", "engine.decode_bw_pct.sala").read
    assert bw(bare) is None and bw({}) is None


def test_the_roofline_reader_counts_kept_rows_not_live_keys():
    bw = common.load_file("layer_metrics", "engine.decode_bw_pct.sala").read
    m = config()
    reqs = [{"prompt_len": 45000, "token_times": [0.0, 10.0], "done": 10.0}
            for _ in range(8)]
    run = {"config": m, "decode_block_len": 8,
           "peaks": {"hbm_bytes_per_s": 819e9},
           "load": {"requests": reqs},
           "metrics_before": _scrape(lightning_layer_steps=0),
           "metrics_after": _scrape(lightning_layer_steps=9),
           "trace": {"t_start": 2.0, "t_stop": 5.0,
                     "modules": {"jit__decode_block_impl(1)": (10, 1.0)}}}
    # 80 steps in 1.0 s: 12.5 ms a step against 7.70 GB / 819 GB/s = 9.40 ms
    least = osl.decode_step_bytes(m, 8, 8 * 45001) / 819e9
    assert bw(run) == pytest.approx(100 * least / 0.0125)
    assert 74 < bw(run) < 76


# ---- the seams and the harness ---------------------------------------------


def test_the_configuration_holds_the_published_keys():
    c = config()
    with open(CATALOG) as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA"]
    assert c["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items() if c.get(k) != v]
    assert sorted(differ) == sorted(c["reduced"]) == [
        "mixer_types", "num_hidden_layers"]
    assert c["reduced_from"]["mixer_types"] == row["config"]["mixer_types"]
    assert c["reduced_from"]["num_hidden_layers"] == 32
    assert c["mixer_types"] == row["config"]["mixer_types"][9:21]
    assert len(c["assumed"]) >= 7 and all(
        any(a.startswith(f"({x})") for a in c["assumed"]) for x in "abcdefg")
    assert c["serve"] == {"slots": 8, "max_seq_len": 65536}
    for key in ("deployment", "departures", "rehearsal"):
        assert c[key]


def test_the_configuration_and_the_cell_load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    c = config()
    assert entry["reduced"] == c["reduced"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "longctx-decode-closed-64k", 1)
    got = common.model_section(c)
    assert list(got)[:9] == list(common.MODEL_KEYS)
    assert got["model_type"] == "minicpm_sala"
    assert got["sparse_config"]["topk"] == 64
    assert (got["first_layer"], got["total_layers"]) == (9, 32)
    assert common.load_reference(c).__file__.endswith("minicpm_sala.py")
    listed = [m["name"] for m in manifest["end_to_end"]
              if CELL in m.get("workloads", ())]
    assert listed[0] == "serve_out_tokens_per_s"
    moves = {m["name"]: m["moves"] for m in manifest["per_layer"]
             if CELL in m.get("workloads", ())}
    assert set(moves.values()) <= set(listed)
    for name in ("engine.decode_bw_pct.sala", "infllm.selected_pct",
                 "infllm.sparse_rows_pct",
                 "lightning.state_updates_per_step"):
        assert moves[name] == "serve_out_tokens_per_s"
        mine, = [m for m in manifest["per_layer"] if m["name"] == name]
        assert mine["workloads"] == [CELL]
    with open(os.path.join(HERE, "..", "traffic",
                           "longctx-decode-closed-64k.json")) as f:
        t = json.load(f)
    assert (t["loop"], t["clients"], t["shapes"]) == ("closed", 8, 8)
    assert t["prompt_len"] == {"dist": "uniform", "min": 32768, "max": 57344}
    assert t["output_len"] == {"dist": "uniform", "min": 6144, "max": 6144}
    # rows on both sides of dense_len, 192 blocks of which 64 are kept
    assert t["check_prompt_len"] == 12288 and t["trace_seconds"] == 3
    assert t["prompt_len"]["max"] + t["output_len"]["max"] \
        < c["serve"]["max_seq_len"]


@pytest.mark.parametrize("trace", [0, 2])
def test_rehearsal_runs_the_cell_end_to_end(trace):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "2", "--trace", str(trace),
         "--rehearse"], cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["compiles_in_window"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    computed = set(out["computed"])
    assert {"serve_out_tokens_per_s", "setup_s"} <= computed
    mine = {"infllm.selected_pct", "infllm.sparse_rows_pct",
            "lightning.state_updates_per_step"}
    assert (mine <= computed) if trace else not (mine & computed)
