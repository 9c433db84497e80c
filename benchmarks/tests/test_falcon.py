"""The Falcon-H1 configuration's pieces of the benchmark, as new cases beside
the files that exist (a PR that adds a cell edits none of them):
``opcount_falcon`` against numbers worked by hand and against the program's
tree, the reference against the program's forward, the new readers on made-up
runs, the configuration and the cell through the seams and the harness. By
hand (``python -m pytest benchmarks/tests/test_falcon.py``)."""

import json
import os
import subprocess
import sys

import jax
import numpy as np

from benchmarks import common, opcount_falcon as of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "falcon-h1-34b-l4"
CELL = NAME + ".serve-longform-decode"
NEW_READERS = ("engine.decode_bw_pct.falcon",
               "kernels.flash_decode_roofline.falcon",
               "attn.keys_per_step.falcon", "mixer.kv_bytes_pct.falcon")
# the readers the cell joins, no file of theirs edited
JOINED = ("kernels.ssm_step_roofline", "ssm.state_updates_per_step",
          "engine.decode_step_ms", "device.idle_pct.serve",
          "batcher.dispatch_gap_ms", "batcher.plan_ms", "batcher.deliver_ms",
          "front.loop_lock_wait_ms", "front.results_ms",
          "engine.issue_operands_ms", "engine.issue_enqueue_ms",
          "engine.sync_wait_ms", "engine.sync_fetch_ms", "batcher.stall_s",
          "engine.device_wait_stall_s", "front.oversleep_s")


def config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_falcon_counts_by_hand():
    m = config()
    p = of.params_by_part(m)
    # q and o 5120 x 2560 each, k and v 5120 x 512 each
    assert p["attention"] == 2 * 13_107_200 + 2 * 2_621_440 == 31_457_280
    # in_proj 5120 x (4096 z + 4096 x + 512 B + 512 C + 32 dt) + out_proj
    # 4096 x 5120 + conv 5120 x 4 + 5120 + dt_bias, A_log, D 3 x 32 + the
    # gated norm 4096
    assert p["mamba"] == 5120 * 9248 + 4096 * 5120 + 5120 * 5 + 96 + 4096 \
        == 68_351_072
    assert p["mlp"] == 3 * 5120 * 21504 == 330_301_440
    assert of.layer_params(m) == 430_120_032
    assert round(100 * p["mlp"] / of.layer_params(m)) == 77
    assert p["embed"] + p["head"] + p["final_norm"] == 2_673_873_920
    assert of.num_params(m) == 4 * 430_120_032 + 2_673_873_920 \
        == 4_394_354_048
    assert round(2 * of.num_params(m) / 1e9, 2) == 8.79
    assert f"{of.num_params(m):,}" in m["deployment"]
    # the uncut model by the same count
    assert round(of.num_params(dict(m, num_hidden_layers=72)) / 1e9, 2) \
        == 33.64
    # state: 32 x 128 x 256 x 4 B a layer, + a conv tail of 3 x 5120 x 2 B
    assert of.layer_state_bytes(m) == 4_194_304
    assert of.state_bytes_per_slot(m) == 4 * (4_194_304 + 30_720)
    assert of.layer_kv_bytes_per_token(m) == 2048
    assert of.kv_bytes_per_token(m) == 8192
    # a slot's state read and written is its K/V read at 4,096 tokens
    assert 2 * of.layer_state_bytes(m) == 4096 * 2048
    slots, window = m["serve"]["slots"], m["serve"]["max_seq_len"]
    resident = (2 * of.num_params(m) + slots * of.state_bytes_per_slot(m)
                + slots * window * of.kv_bytes_per_token(m))
    assert round(resident / 1e9, 2) == 13.09
    # a step at 64 slots of 2,500 tokens: weights less the embedding table
    # 6.12 GB (the head 2.67 of it), the state twice 2.16 GB, K/V 1.31 GB
    step = of.decode_step_bytes(m, 64, 64 * 2500)
    assert round(2 * (of.num_params(m) - p["embed"]) / 1e9, 2) == 6.11
    assert round(step / 1e9, 2) == 9.59


def test_falcon_counts_are_the_programs_tree():
    from picotron_tpu.config import Config
    from picotron_tpu.models import falcon_h1 as fh

    m = config()
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True}, "model": common.model_section(m),
        "training": {"seq_length": 6144}, "dataset": {"name": "synthetic"}})
    assert fh.num_params(cfg.model) == of.num_params(m)
    cache = jax.eval_shape(lambda: fh.init_cache(cfg.model, 64, 6144))
    per_slot = sum(np.prod(cache[n].shape[2:]) * cache[n].dtype.itemsize
                   * cache[n].shape[0] for n in ("ssm", "conv"))
    assert per_slot == of.state_bytes_per_slot(m)
    assert cache["k"].shape == cache["v"].shape == (4, 64, 6144, 4, 128)
    assert cache["ssm"].shape == (4, 64, 32, 128, 256)
    assert 2 * np.prod(cache["k"].shape[3:]) * 2 * 4 \
        == of.kv_bytes_per_token(m)


def test_falcon_reference_is_the_programs_forward():
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import test_falcon_h1 as t

    _, engine, params = t.make_engine()
    seq, got, _ = t.program_logits(engine, params, t.PROMPT)
    want = t.reference_rows(params, seq, len(t.PROMPT))
    assert t.worst_rel_err(got, want) < 1e-3
    ids = np.asarray([t.PROMPT])
    loss = t.ref.loss(params, ids[:, :-1], ids[:, 1:], dict(t.TOY))
    assert 4.0 < loss < 8.0  # ln 256 = 5.5: an untrained model
    # the head a block of columns at a time is the head whole
    t.ref.COL_BLOCK, whole = 100, t.ref.COL_BLOCK
    try:
        blocks = t.reference_rows(params, seq, len(t.PROMPT))
    finally:
        t.ref.COL_BLOCK = whole
    np.testing.assert_allclose(blocks, want, atol=1e-6)


def made_up_run(m):
    text = lambda keys, attn, upd, ssm: (
        f"picotron_attn_keys_read_total {keys}\n"
        f"picotron_attn_layer_steps_total {attn}\n"
        f"picotron_ssm_state_updates_total {upd}\n"
        f"picotron_ssm_layer_steps_total {ssm}\n")
    # 64 slots streaming at 2,500 tokens each, 10 blocks of 8 steps, four
    # layers a step
    reqs = [{"prompt_len": 2499, "token_times": [0.0, 10.0], "done": 10.0}
            for _ in range(64)]
    return {"config": m, "metrics_before": text(0, 0, 0, 0),
            "metrics_after": text(320 * 64 * 2500, 320, 320 * 64, 320),
            "peaks": {"hbm_bytes_per_s": 819e9}, "decode_block_len": 8,
            "load": {"requests": reqs},
            "trace": {"t_start": 1.0, "t_stop": 4.0,
                      "modules": {"jit__decode_block_impl(1)": (10, 1.2)},
                      "ops": {"flash_decode_attention.3": (320, 0.16),
                              "ssm_step.2": (320, 0.256)}}}


def test_falcon_readers_on_a_made_up_run():
    read = {n: common.load_file("layer_metrics", n).read
            for n in NEW_READERS + ("kernels.ssm_step_roofline",
                                    "ssm.state_updates_per_step")}
    run = made_up_run(config())
    assert read["attn.keys_per_step.falcon"](run) == 64 * 2500
    # K/V 2,500 x 2,048 B against the state's 8.39 MB a slot
    assert round(read["mixer.kv_bytes_pct.falcon"](run), 1) == 37.9
    assert read["ssm.state_updates_per_step"](run) == 64
    # 9.59 GB a step at 819 GB/s is 11.7 ms of the 15 the step took
    assert 77.5 < read["engine.decode_bw_pct.falcon"](run) < 78.5
    # one layer's K/V 0.328 GB is 0.400 ms of the 0.5 a call took
    assert 79.5 < read["kernels.flash_decode_roofline.falcon"](run) < 80.5
    # one layer's state twice 0.537 GB is 0.655 ms of the 0.8 a call took
    assert 81.5 < read["kernels.ssm_step_roofline"](run) < 82.5
    # a program without the block (the parent): nothing, and no error
    bare = dict(run, metrics_after=run["metrics_before"],
                trace=dict(run["trace"], ops={"fusion.1": (10, 1.0)}))
    assert all(read[n](bare) is None for n in NEW_READERS)
    assert all(read[n]({"config": config()}) is None for n in NEW_READERS)
    # at 4,096 tokens a slot the two kinds of cache cost the same
    even = dict(run, metrics_after=run["metrics_after"].replace(
        str(320 * 64 * 2500), str(320 * 64 * 4096)))
    assert read["mixer.kv_bytes_pct.falcon"](even) == 50.0


def test_falcon_cell_through_the_seams():
    m = config()
    model = common.model_section(m)
    assert model["model_type"] == "falcon_h1"
    assert model["mamba_d_ssm"] == 4096 and model["num_hidden_layers"] == 4
    assert len(model["ssm_multipliers"]) == 5
    assert common.load_reference(m).__file__.endswith("falcon_h1.py")
    man = manifest()
    cell, = [w for w in man["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert man["workloads"][-1] is cell and len(man["workloads"]) == 15
    assert sum(w["chips"] == 4 for w in man["workloads"]) == 1
    entry, = [c for c in man["configs"] if c["name"] == NAME]
    assert entry["reduced"] == m["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == m["source"] and len(entry["why"]) <= 200
    with open(os.path.join(HERE, "..", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert mix["clients"] == mix["shapes"] == m["serve"]["slots"] == 64
    assert mix["prompt_len"] == {"dist": "log_uniform", "min": 256,
                                 "max": 1792}
    assert mix["check_prompt_len"] == 1536
    # the check reads the float32 state beside the logits
    assert mix["runner"] == "serve_state"
    assert mix["prompt_len"]["max"] + 4096 <= m["serve"]["max_seq_len"]
    # the cell lists exactly the new readers and the ones it joins
    listed = {x["name"] for x in man["per_layer"] if CELL in x["workloads"]}
    assert listed == set(NEW_READERS + JOINED)
    assert all(x["workloads"] == [CELL] for x in man["per_layer"]
               if x["name"] in NEW_READERS)
    ends = {x["name"] for x in man["end_to_end"]
            if CELL in x.get("workloads", (CELL,))}
    assert ends == {"serve_out_tokens_per_s", "serve_itl_p99_ms", "setup_s"}


def test_falcon_rehearsal_of_the_cell():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "3", "--trace", "2", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    # the logits' rms and the state's two rows behind the logits' five
    assert p.stderr.count("logits state of slot 0") == 2
    assert p.stderr.count("rows, root mean square") == 1
    assert "FAIL" not in p.stderr
    # the CPU has no device trace and no kernel: every reader of host
    # clocks and counters that the cell lists found something to read
    on_cpu = {"serve_out_tokens_per_s", "serve_itl_p99_ms", "setup_s",
              "attn.keys_per_step.falcon", "mixer.kv_bytes_pct.falcon",
              "ssm.state_updates_per_step", "batcher.dispatch_gap_ms",
              "batcher.plan_ms", "batcher.deliver_ms",
              "front.loop_lock_wait_ms", "front.results_ms",
              "engine.issue_operands_ms", "engine.issue_enqueue_ms",
              "engine.sync_wait_ms", "engine.sync_fetch_ms",
              "batcher.stall_s", "engine.device_wait_stall_s",
              "front.oversleep_s"}
    assert set(out["computed"]) == on_cpu
