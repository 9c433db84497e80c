"""The Nemotron-H configuration's pieces of the benchmark, as new cases
beside the files that exist (a PR that adds a cell edits none of them):
``opcount_nemotron`` against numbers worked by hand and against the program's
tree, the reference against the program's forward, the new readers on made-up
runs, the configuration and the cell through the seams and the harness. By
hand (``python -m pytest benchmarks/tests/test_nemotron.py``)."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmarks import common, opcount_nemotron as on

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "nemotron-3-super-ep4-l11"
CELL = NAME + ".serve-reasoning-decode"


def config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


def test_nemotron_counts_by_hand():
    m = config()
    p = on.params_by_part(m)
    # in_proj 4096 x (8192 z + 10240 xBC + 128 dt) + out_proj 8192 x 4096 +
    # conv 10240 x 4 + 10240 + dt_bias, A_log, D 3 x 128 + gated norm 8192 +
    # the layer's norm 4096
    assert p["M"] == 4096 * 18560 + 8192 * 4096 + 10240 * 5 + 384 + 8192 + 4096
    assert round(p["M"] / 1e6, 1) == 109.6
    # q and o 4096 x 4096 each, k and v 4096 x 256 each, the norm
    assert p["*"] == 2 * 16_777_216 + 2 * 1_048_576 + 4096
    # router 4096 x 512 + bias 512, W_down and W_up 4096 x 1024 each, the
    # shared expert 2 x 4096 x 5376, the norm
    assert p["E"] == 2_097_152 + 512 + 2 * 4_194_304 + 44_040_192 + 4096
    assert p["routed_expert"] == 2 * 1024 * 2688 == 5_505_024
    assert on.kind_counts(m) == {"M": 5, "E": 5, "*": 1}
    assert on.num_params(m) == 4_648_163_712
    assert round(2 * on.num_params(m) / 1e9, 2) == 9.30
    # state: 5 x (128 x 64 x 128 x 4 B + 3 x 10240 x 2 B); K/V: 2 x 2 x 128 x 2
    assert on.state_bytes_per_slot(m) == 5 * (4_194_304 + 61_440)
    assert on.kv_bytes_per_token(m) == 1024
    assert round(128 * on.state_bytes_per_slot(m) / 1e9, 2) == 2.72
    assert 128 * 8192 * on.kv_bytes_per_token(m) == 1_073_741_824
    # a step at 128 slots and 2,000 tokens each: weights less the embedding
    # table 9.03 GB, the state twice 5.45 GB, K/V 0.26 GB
    step = on.decode_step_bytes(m, 128, 128 * 2000)
    assert round((2 * on.num_params(m) - 2 * p["embed"]) / 1e9, 2) == 9.03
    assert round(step / 1e9, 1) == 14.7
    # one layer's pass: 128 x 11.01 MB of experts, 128 rows of 1024 x 6 B
    assert round(on.pipelined_pass_bytes(m, 128) / 1e9, 3) == 1.410


def test_nemotron_counts_are_the_programs_tree():
    from picotron_tpu.config import Config
    from picotron_tpu.models import nemotron_h as nh

    m = config()
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True}, "model": common.model_section(m),
        "training": {"seq_length": 8192}, "dataset": {"name": "synthetic"}})
    assert nh.num_params(cfg.model) == on.num_params(m)
    cache = jax.eval_shape(lambda: nh.init_cache(cfg.model, 128, 8192))
    per_slot = sum(np.prod(cache[n].shape[2:]) * cache[n].dtype.itemsize
                   * cache[n].shape[0] for n in ("ssm", "conv"))
    assert per_slot == on.state_bytes_per_slot(m)
    assert cache["k"].shape == (1, 128, 8192, 2, 128)
    assert 2 * np.prod(cache["k"].shape[3:]) * 2 == on.kv_bytes_per_token(m)


def test_nemotron_reference_is_the_programs_forward():
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import test_nemotron_h as t

    _, engine, params = t.make_engine()
    seq, got, _ = t.program_logits(engine, params, t.PROMPT)
    want = t.reference_rows(params, seq, len(t.PROMPT))
    assert t.worst_rel_err(got, want) < 1e-3
    ids = np.asarray([t.PROMPT])
    loss = t.ref.loss(params, ids[:, :-1], ids[:, 1:], dict(t.TOY))
    assert 4.0 < loss < 8.0  # ln 256 = 5.5: an untrained model


def test_nemotron_readers_on_a_made_up_run():
    read = {n: common.load_file("layer_metrics", n).read for n in (
        "engine.decode_bw_pct.nemotron", "moe.experts_hit_pct.nemotron",
        "kernels.pipelined_experts_roofline.nemotron")}
    text = lambda hit, moe, ssm: (
        f"picotron_moe_experts_hit_total {hit}\n"
        f"picotron_moe_layer_steps_total {moe}\n"
        f"picotron_ssm_layer_steps_total {ssm}\n")
    reqs = [{"prompt_len": 1000, "token_times": [0.0, 10.0], "done": 10.0}
            for _ in range(128)]
    run = {"config": config(), "metrics_before": text(0, 0, 0),
           "metrics_after": text(5 * 8 * 127.5, 5 * 8, 5 * 8),
           "peaks": {"hbm_bytes_per_s": 819e9}, "decode_block_len": 8,
           "load": {"requests": reqs},
           "trace": {"t_start": 1.0, "t_stop": 4.0,
                     "modules": {"jit__decode_block_impl(1)": (10, 2.0)},
                     "ops": {"pipelined_experts.3": (400, 0.8)}}}
    assert round(read["moe.experts_hit_pct.nemotron"](run), 2) == 99.61
    # 14.6 GB a step at 819 GB/s is 17.8 ms of the 25 the step took
    bw = read["engine.decode_bw_pct.nemotron"](run)
    assert 70.0 < bw < 72.5
    # 1.41 GB a pass at 819 GB/s is 1.72 ms of the 2.0 a call took
    assert 85.5 < read["kernels.pipelined_experts_roofline.nemotron"](run) \
        < 86.5
    # a program without the block (the parent): nothing, and no error
    bare = dict(run, metrics_after=run["metrics_before"],
                trace=dict(run["trace"], ops={"fusion.1": (10, 1.0)}))
    assert all(r(bare) is None for r in read.values())
    assert all(r({"config": config()}) is None for r in read.values())
    granite = dict(run, config={"n_routed_experts": 8})
    assert read["kernels.pipelined_experts_roofline.nemotron"](granite) \
        is None


def test_nemotron_cell_through_the_seams():
    m = config()
    model = common.model_section(m)
    assert model["model_type"] == "nemotron_h"
    assert model["hybrid_override_pattern"] == "EMEMEMEMEM*"
    assert model["rms_norm_eps"] == m["layer_norm_epsilon"]
    assert common.load_reference(m).__file__.endswith("nemotron_h.py")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["reduced"] == m["reduced"] and len(entry["why"]) <= 200
    with open(os.path.join(HERE, "..", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert mix["clients"] == mix["shapes"] == m["serve"]["slots"]
    assert mix["prompt_len"] == {"dist": "log_uniform", "min": 256,
                                 "max": 3584}
    assert mix["check_prompt_len"] == 1536
    assert mix["prompt_len"]["max"] + 4096 <= m["serve"]["max_seq_len"]


def test_nemotron_rehearsal_of_the_cell():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "2", "--trace", "2", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    # ``<=``: a later PR's reader joins the cell without breaking this
    assert {"serve_out_tokens_per_s", "serve_itl_p99_ms", "setup_s",
            "moe.held_assignments_per_step", "moe.experts_hit_pct.nemotron",
            "ssm.state_updates_per_step", "batcher.dispatch_gap_ms",
            "batcher.plan_ms", "batcher.deliver_ms",
            "front.loop_lock_wait_ms", "front.results_ms",
            "engine.issue_operands_ms", "engine.issue_enqueue_ms",
            "engine.sync_wait_ms", "engine.sync_fetch_ms", "batcher.stall_s",
            "engine.device_wait_stall_s", "front.oversleep_s"} \
        <= set(out["computed"])
