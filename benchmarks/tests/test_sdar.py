"""The SDAR-MoE configuration's pieces of the benchmark, as new cases beside
the files that exist (a PR that adds a cell edits none of them):
``opcount_sdar`` against numbers worked by hand and against the program's
tree, the new readers on made-up runs, the configuration and the cell through
the seams and the harness, the new runner's check at toy size. By hand
(``python -m pytest benchmarks/tests/test_sdar.py``)."""

import json
import os
import subprocess
import sys

import jax

from benchmarks import common, opcount_sdar as osd

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "sdar-30b-a3b-ep8-l12"
CELL = NAME + ".serve-blockdiff-decode"
READERS = ("diffusion.tokens_per_forward", "diffusion.commit_forward_pct",
           "engine.forward_ms.sdar", "engine.forward_bw_pct.sdar",
           "moe.held_assignments_per_step.sdar",
           "kernels.block_decode_roofline.sdar")


def config():
    with open(os.path.join(HERE, "..", "configs", NAME + ".json")) as f:
        return json.load(f)


def test_sdar_counts_by_hand():
    m = config()
    p = osd.params_by_part(m)
    # q and o 2048 x 4096 each, k and v 2048 x 512 each, two norms of 128
    assert p["attention"] == 2 * 8_388_608 + 2 * 1_048_576 + 256
    assert p["router"] == 2048 * 128 and p["norms"] == 4096
    assert p["routed_expert"] == 3 * 2048 * 768 == 4_718_592
    assert osd.layer_params(m) == 94_638_336
    assert p["embed"] + p["head"] + p["final_norm"] == 77_793_280
    assert osd.num_params(m) == 12 * 94_638_336 + 77_793_280 == 1_213_453_312
    assert round(2 * osd.num_params(m) / 1e9, 2) == 2.43
    assert f"{osd.num_params(m):,}" in m["deployment"]
    # the uncut model by the same count
    whole = dict(m, num_hidden_layers=48, num_experts=128, ep_size=1,
                 vocab_size=151936)
    assert round(osd.num_params(whole) / 1e9, 1) == 30.5
    assert osd.kv_bytes_per_row(m) == 2048
    slots, window = m["serve"]["slots"], m["serve"]["max_seq_len"]
    assert osd.cache_bytes(m, slots, window) == 393_216 * 24_576
    assert round((osd.cache_bytes(m, slots, window)
                  + 2 * osd.num_params(m)) / 1e9, 2) == 12.09
    # a forward over 32 slots of 4,300 tokens: weights less the embedding
    # table 2.35 GB, K and V of 137,600 tokens in 12 layers 3.38 GB
    live = 32 * 4300
    assert round(2 * (osd.num_params(m) - p["embed"]) / 1e9, 2) == 2.35
    assert round(12 * osd.layer_kv_bytes(m, live) / 1e9, 2) == 3.38
    assert round(osd.forward_bytes(m, live) / 1e9, 2) == 5.73
    # 128 rows through twelve layers of sixteen experts, and the head
    assert 0.35e12 < osd.forward_flops(m, 128, live) < 0.45e12


def test_sdar_counts_are_the_programs_tree():
    from picotron_tpu.config import Config
    from picotron_tpu.models import sdar_moe

    m = config()
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True}, "model": common.model_section(m),
        "training": {"seq_length": 12288}, "dataset": {"name": "synthetic"}})
    assert sdar_moe.num_params(cfg.model) == osd.num_params(m)
    cache = jax.eval_shape(lambda: sdar_moe.init_cache(cfg.model, 32, 12288))
    assert cache["k"].shape == cache["v"].shape == (12, 32, 12288, 4, 128)
    assert 2 * 4 * 128 * 2 == osd.kv_bytes_per_row(m)


def test_sdar_readers_on_a_made_up_run():
    read = {n: common.load_file("layer_metrics", n).read for n in READERS}
    text = lambda rounds: (
        f'picotron_dispatch_total{{kind="blocks"}} {rounds}\n'
        f'picotron_dispatch_total{{kind="prefill_chunk"}} 7\n'
        f'picotron_diffusion_forwards_total{{kind="denoise"}} {8 * rounds}\n'
        f'picotron_diffusion_forwards_total{{kind="commit"}} {2 * rounds}\n'
        f"picotron_diffusion_positions_unmasked_total {256 * rounds}\n"
        f"picotron_diffusion_rows_total {10 * 128 * rounds}\n"
        f"picotron_moe_assignments_total {10 * 12 * 128 * rounds}\n"
        f"picotron_moe_layer_steps_total {10 * 12 * rounds}\n")
    reqs = [{"prompt_len": 1300, "token_times": [0.0, 10.0], "done": 10.0}
            for _ in range(32)]
    run = {"config": config(), "metrics_before": text(0),
           "metrics_after": text(240),
           "peaks": {"hbm_bytes_per_s": 819e9}, "decode_block_len": 8,
           "load": {"requests": reqs},
           "trace": {"t_start": 1.0, "t_stop": 4.0,
                     "modules": {"jit__blocks_impl(7)": (24, 2.88),
                                 "jit__decode_block_impl(1)": (9, 9.0)},
                     "ops": {"fusion.3": (400, 0.8),
                             "flash_decode_attention.12": (2304, 0.9216),
                             "flash_decode_attention.11": (576, 0.2304)}}}
    assert read["diffusion.tokens_per_forward"](run) == 0.8
    assert read["diffusion.commit_forward_pct"](run) == 20.0
    assert read["moe.held_assignments_per_step.sdar"](run) == 8.0
    # 24 runs of ten forwards in 2.88 s: 12 ms a forward
    assert abs(read["engine.forward_ms.sdar"](run) - 12.0) < 1e-9
    # 2.35 GB of weights + 12 layers x 32 x 1,301 tokens x 2,048 B = 3.37 GB
    # at 819 GB/s is 4.12 ms of the 12
    assert 34.0 < read["engine.forward_bw_pct.sdar"](run) < 34.6
    # one layer's K and V of 41,632 tokens: 0.104 ms of the 0.4 ms a call took
    assert 25.5 < read["kernels.block_decode_roofline.sdar"](run) < 26.5
    no_kernel = dict(run, trace=dict(run["trace"], ops={"fusion.3": (4, 1.)}))
    assert read["kernels.block_decode_roofline.sdar"](no_kernel) is None
    # a program without the counters (the parent): nothing, and no error
    bare = dict(run, metrics_after=run["metrics_before"])
    assert all(r(bare) is None for r in read.values())
    assert all(r({"config": config()}) is None for r in read.values())


def test_sdar_cell_through_the_seams():
    m = config()
    model = common.model_section(m)
    assert model["model_type"] == "sdar_moe"
    assert (model["block_length"], model["denoising_steps"],
            model["remasking"], model["confidence_threshold"],
            model["mask_token_id"]) == (4, 4, "low_confidence_dynamic", 0.9,
                                        18991)
    # the harness passes them for every configuration; no layer reads it
    assert model["intermediate_size"] == 6144
    assert common.load_reference(m).__file__.endswith("sdar_moe.py")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert len(manifest["workloads"]) == 14 and len(manifest["configs"]) == 11
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["reduced"] == m["reduced"] and len(entry["why"]) <= 200
    assert entry["source"] == m["source"]
    listed = {x["name"] for x in manifest["per_layer"]
              if CELL in x.get("workloads", ())}
    assert set(READERS) <= listed and "engine.decode_step_ms" not in listed
    assert all(x["workloads"] == [CELL] for x in manifest["per_layer"]
               if x["name"] in READERS)
    # tokens/s spread past half its bound in two sets of four (PERF.md
    # section 2), so the cell lists the inter-token tail alone, and every
    # reader it lists moves that
    assert [x["name"] for x in manifest["end_to_end"]
            if CELL in x.get("workloads", ())] == ["serve_itl_p99_ms"]
    assert all(x["moves"] == "serve_itl_p99_ms"
               for x in manifest["per_layer"] if x["name"] in listed)
    with open(os.path.join(HERE, "..", "traffic",
                           cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert mix["runner"] == "serve_blocks"
    assert mix["clients"] == mix["shapes"] == m["serve"]["slots"] == 32
    assert mix["prompt_len"] == {"dist": "log_uniform", "min": 256,
                                 "max": 3584}
    assert mix["output_len"] == {"dist": "uniform", "min": 8192, "max": 8192}
    # three whole chunks of 512 and a remainder of 2 given positions
    assert mix["check_prompt_len"] == 1538 == 3 * 512 + 2
    assert mix["prompt_len"]["max"] + 8192 <= m["serve"]["max_seq_len"]
    # every number of the catalog's row under its own key, but the cuts
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    differ = {k for k, v in published.items() if m[k] != v}
    assert differ == set(m["reduced"]) - {"ep_size"}
    assert all(m["reduced_from"][k] == published[k] for k in differ)
    for part in ("source", "reduced", "reduced_from", "deployment",
                 "assumed", "departures"):
        assert m[part]


def test_sdar_rehearsal_of_the_cell():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "4", "--trace", "2", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    # the check's two parts, seven denoise forwards each
    assert "logits forward 13 (context 16)" in p.stderr
    # their error as a whole, and the real round's four streams
    assert "logits all 34 rows, root mean square for max" in p.stderr
    assert p.stderr.count("logits round, ") == 5
    assert "FAIL" not in p.stderr
    # ``<=``: a later PR's reader joins the cell without breaking this; the
    # three readers of the device trace find nothing to read on the CPU
    assert {"serve_itl_p99_ms", "setup_s",
            "diffusion.tokens_per_forward", "diffusion.commit_forward_pct",
            "moe.held_assignments_per_step.sdar", "batcher.dispatch_gap_ms",
            "batcher.plan_ms", "batcher.deliver_ms",
            "front.loop_lock_wait_ms", "front.results_ms",
            "engine.issue_operands_ms", "engine.issue_enqueue_ms",
            "engine.sync_wait_ms", "engine.sync_fetch_ms"} \
        <= set(out["computed"])
    assert not {"engine.decode_step_ms", "engine.decode_bw_pct",
                "serve_out_tokens_per_s"} & set(out["computed"])
