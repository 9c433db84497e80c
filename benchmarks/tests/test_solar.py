"""The Solar Open 2 configuration's pieces of the benchmark, as new cases
beside the files that exist (a PR that adds a cell edits none of them):
``opcount_solar`` against numbers worked by hand and against the program's
tree, the reference against the program's forward, the new readers on made-up
runs, the configuration and the cell through the seams and the harness. By
hand (``python -m pytest benchmarks/tests/test_solar.py``)."""

import json
import os
import subprocess
import sys

import jax
import numpy as np

from benchmarks import common, opcount_solar as osl

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "solar-open2-ep16-l8"
CELL = NAME + ".serve-reasoning-decode"


def config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


def test_solar_counts_by_hand():
    m = config()
    p = osl.params_by_part(m)
    # W_q | W_k | W_v 4096 x 24576, W_out 8192 x 4096, the decay's and the
    # gate's pair 2 x (4096 x 128 + 128 x 8192), W_b 4096 x 64, taps 24576 x
    # 4, A_log 64, dt_bias 8192, the head's norm 128
    assert p["kda"] == (100_663_296 + 33_554_432 + 2 * 1_572_864 + 262_144
                        + 98_304 + 64 + 8192 + 128)
    assert round(p["kda"] / 1e6, 2) == 137.73
    # q, g and o 4096 x 8192 each, k and v 4096 x 1024 each
    assert p["gqa"] == 3 * 33_554_432 + 2 * 4_194_304
    # router 4096 x 320 + bias 320, the shared expert 3 x 4096 x 1280, the
    # layer's two norms
    assert p["experts"] == 1_310_720 + 320 + 15_728_640 + 8192
    assert p["routed_expert"] == 3 * 4096 * 1280 == 15_728_640
    assert osl.kind_counts(m) == {"gqa": 2, "kda": 6}
    assert osl.num_params(m) == 3_898_793_600
    assert round(2 * osl.num_params(m) / 1e9, 2) == 7.80
    # state: 6 x (64 x 128 x 128 x 4 B + 3 x 24576 x 2 B); K/V: 2 layers x 8
    # heads x 128 x K and V x 2 B
    assert osl.layer_state_bytes(m) == 4_194_304
    assert osl.state_bytes_per_slot(m) == 6 * (4_194_304 + 147_456)
    assert osl.kv_bytes_per_token(m) == 8192
    assert round(64 * osl.state_bytes_per_slot(m) / 1e9, 2) == 1.67
    assert round(64 * 8192 * osl.kv_bytes_per_token(m) / 1e9, 2) == 4.29
    # a step at 64 slots and 2,800 tokens each: weights less the embedding
    # table 7.60 GB, the state twice 3.33 GB, K/V 1.47 GB
    step = osl.decode_step_bytes(m, 64, 64 * 2800)
    assert round((2 * osl.num_params(m) - 2 * p["embed"]) / 1e9, 2) == 7.60
    assert round(step / 1e9, 1) == 12.4
    # the other form of the projections: one matrix each
    full = osl.params_by_part(dict(m, kda_use_full_proj=True))
    assert full["kda"] - p["kda"] == 2 * (4096 * 8192 - 1_572_864)


def test_solar_counts_are_the_programs_tree():
    from picotron_tpu.config import Config
    from picotron_tpu.models import solar_open2 as so

    m = config()
    for change in ({}, {"kda_use_full_proj": True}, {"use_gqa_gate": False}):
        mm = dict(m, **change)
        cfg = Config.from_dict({
            "distributed": {"use_cpu": True},
            "model": common.model_section(mm),
            "training": {"seq_length": 8192},
            "dataset": {"name": "synthetic"}})
        assert so.num_params(cfg.model) == osl.num_params(mm), change
    cache = jax.eval_shape(lambda: so.init_cache(cfg.model, 64, 8192))
    per_slot = sum(np.prod(cache[n].shape[2:]) * cache[n].dtype.itemsize
                   * cache[n].shape[0] for n in ("kda", "conv"))
    assert per_slot == osl.state_bytes_per_slot(m)
    assert cache["kda"].shape == (6, 64, 64, 128, 128)
    assert cache["conv"].shape == (6, 64, 3, 24576)
    assert cache["k"].shape == (2, 64, 8192, 8, 128)
    assert cache["k"].shape[0] * 2 * np.prod(cache["k"].shape[3:]) * 2 \
        == osl.kv_bytes_per_token(m)


def test_solar_reference_is_the_programs_forward():
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import test_solar_open2 as t

    _, engine, params = t.make_engine()
    seq, got, _ = t.program_logits(engine, params, t.PROMPT)
    want = t.reference_rows(params, seq, len(t.PROMPT))
    assert t.worst_rel_err(got, want) < 1e-3
    ids = np.asarray([t.PROMPT])
    loss = t.ref.loss(params, ids[:, :-1], ids[:, 1:], dict(t.TOY))
    assert 4.0 < loss < 8.0  # ln 256 = 5.5: an untrained model


def test_solar_readers_on_a_made_up_run():
    read = {n: common.load_file("layer_metrics", n).read for n in (
        "engine.decode_bw_pct.solar", "moe.experts_hit_pct.solar",
        "kda.state_updates_per_step", "kernels.flash_decode_roofline.solar")}
    text = lambda hit, moe, kda, upd: (
        f"picotron_moe_experts_hit_total {hit}\n"
        f"picotron_moe_layer_steps_total {moe}\n"
        f"picotron_kda_layer_steps_total {kda}\n"
        f"picotron_kda_state_updates_total {upd}\n")
    reqs = [{"prompt_len": 1800, "token_times": [0.0, 10.0], "done": 10.0}
            for _ in range(64)]
    run = {"config": config(), "metrics_before": text(0, 0, 0, 0),
           "metrics_after": text(8 * 8 * 16, 8 * 8, 6 * 8, 6 * 8 * 64),
           "peaks": {"hbm_bytes_per_s": 819e9}, "decode_block_len": 8,
           "load": {"requests": reqs},
           "trace": {"t_start": 1.0, "t_stop": 4.0,
                     "modules": {"jit__decode_block_impl(1)": (10, 1.6)},
                     "ops": {"fusion.3": (400, 0.8),
                             "flash_decode_attention.22": (80, 0.08),
                             "flash_decode_attention.23": (80, 0.08)}}}
    assert read["moe.experts_hit_pct.solar"](run) == 80.0
    assert read["kda.state_updates_per_step"](run) == 64.0
    # 7.60 + 3.33 + 0.94 GB a step at 819 GB/s is 14.5 ms of the 20 it took
    assert 72.0 < read["engine.decode_bw_pct.solar"](run) < 73.0
    # one GQA layer's K and V of 64 x 1,801 tokens at 4,096 B: 0.58 ms of the
    # 1 ms a call took (the dense block's reader would divide by 8 layers)
    assert 57.0 < read["kernels.flash_decode_roofline.solar"](run) < 58.0
    no_kernel = dict(run, trace=dict(run["trace"], ops={"fusion.3": (4, 1.)}))
    assert read["kernels.flash_decode_roofline.solar"](no_kernel) is None
    # a program without the block (the parent): nothing, and no error
    bare = dict(run, metrics_after=run["metrics_before"])
    assert all(r(bare) is None for r in read.values())
    assert all(r({"config": config()}) is None for r in read.values())


def test_solar_cell_through_the_seams():
    m = config()
    model = common.model_section(m)
    assert model["model_type"] == "solar_open2"
    assert model["gqa_layers"] == [0, 4]
    assert model["linear_attn_config"]["num_heads"] == 64
    # the harness passes them for every configuration; no layer reads them
    assert model["intermediate_size"] == 10240 and model["rope_theta"] == 10000
    assert common.load_reference(m).__file__.endswith("solar_open2.py")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["reduced"] == m["reduced"] and len(entry["why"]) <= 200
    assert entry["source"] == m["source"]
    with open(os.path.join(HERE, "..", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert mix["clients"] == mix["shapes"] == m["serve"]["slots"]
    assert mix["prompt_len"] == {"dist": "log_uniform", "min": 256,
                                 "max": 3584}
    # three whole chunks of 512, as ISSUE 58 names it: state and conv tails
    # carried twice (PERF.md section 6 has what the check sees of each)
    assert mix["check_prompt_len"] == 1536
    assert mix["prompt_len"]["max"] + 4096 <= m["serve"]["max_seq_len"]
    # every number of the catalog's row under its own key, but the cuts
    published = {
        "partial_rotary_factor": 1, "hidden_size": 4096,
        "num_hidden_layers": 48, "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "gqa_interval": 3, "n_routed_experts": 320, "n_shared_experts": 1,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    differ = {k for k, v in published.items() if m[k] != v}
    assert differ == set(m["reduced"]) - {"gqa_layers", "ep_size"}
    assert all(m["reduced_from"][k] == published[k] for k in differ)


def test_solar_rehearsal_of_the_cell():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "4", "--trace", "2", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    # ``<=``: a later PR's reader joins the cell without breaking this
    assert {"serve_out_tokens_per_s", "serve_itl_p99_ms", "setup_s",
            "moe.held_assignments_per_step", "moe.experts_hit_pct.solar",
            "kda.state_updates_per_step", "batcher.dispatch_gap_ms",
            "batcher.plan_ms", "batcher.deliver_ms",
            "front.loop_lock_wait_ms", "front.results_ms",
            "engine.issue_operands_ms", "engine.issue_enqueue_ms",
            "engine.sync_wait_ms", "engine.sync_fetch_ms", "batcher.stall_s",
            "engine.device_wait_stall_s", "front.oversleep_s"} \
        <= set(out["computed"])
