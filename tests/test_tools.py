"""Tooling-layer tests: config generator, sweep scheduler + status triage,
metrics extractor (the reference's L6 surface, SURVEY.md §3.5)."""

import json
import os
import subprocess
import sys

import pytest

from picotron_tpu.config import Config
from picotron_tpu.tools import create_config as cc
from picotron_tpu.tools import extract_metrics as em
from picotron_tpu.tools import submit_jobs as sj


# ---------------------------------------------------------------- create_config


def test_create_config_writes_valid_config(tmp_path):
    path = cc.create_single_config(
        out_dir=str(tmp_path), exp_name="exp1", dp=2, tp=2,
        model_name="HuggingFaceTB/SmolLM-1.7B", seq_len=512, mbs=4,
        grad_acc_steps=8, use_cpu=True)
    cfg = Config.from_json(path)
    assert cfg.distributed.dp_size == 2 and cfg.distributed.tp_size == 2
    assert cfg.model.hidden_size == 2048  # SmolLM-1.7B from the shape table
    assert cfg.training.seq_length == 512
    assert cfg.global_batch_size == 4 * 8 * 2


def test_create_config_shape_overrides_win(tmp_path):
    path = cc.create_single_config(
        out_dir=str(tmp_path), exp_name="exp2",
        model_name="HuggingFaceTB/SmolLM-1.7B", num_hidden_layers=5,
        seq_len=128, use_cpu=True)
    cfg = Config.from_json(path)
    assert cfg.model.num_hidden_layers == 5
    assert cfg.model.hidden_size == 2048


def test_known_model_shapes_all_validate():
    """Every offline shape-table entry builds a valid Config (the table is
    the zero-egress path to each supported model family)."""
    from picotron_tpu.config import Config
    from picotron_tpu.models import llama
    from picotron_tpu.tools.create_config import KNOWN_MODEL_SHAPES

    for name, shape in KNOWN_MODEL_SHAPES.items():
        cfg = Config.from_dict({
            "distributed": {"use_cpu": True},
            "model": dict(shape, name=name, dtype="float32",
                          attention_impl="sdpa"),
            "training": {"seq_length": 32, "micro_batch_size": 1},
            "dataset": {"name": "synthetic"},
        })
        assert llama.num_params(cfg.model) > 1e8, name
        # GQA geometries must divide cleanly
        assert (cfg.model.num_attention_heads
                % cfg.model.num_key_value_heads == 0), name


def test_create_config_rejects_bad_topology(tmp_path):
    with pytest.raises(ValueError):
        cc.create_single_config(
            out_dir=str(tmp_path), exp_name="bad",
            model_name="HuggingFaceTB/SmolLM-1.7B", tp=7, use_cpu=True)


def test_create_config_unknown_model_full_override_offline(tmp_path):
    # An unknown model with a full shape override must not touch the network.
    path = cc.create_single_config(
        out_dir=str(tmp_path), exp_name="custom",
        model_name="mycorp/custom-tiny", num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, hidden_size=32,
        intermediate_size=64, vocab_size=128, seq_len=64, use_cpu=True)
    cfg = Config.from_json(path)
    assert cfg.model.hidden_size == 32 and cfg.model.vocab_size == 128


def test_create_config_overwrite(tmp_path):
    kw = dict(out_dir=str(tmp_path), exp_name="dup",
              model_name="HuggingFaceTB/SmolLM-135M", seq_len=128, use_cpu=True)
    cc.create_single_config(**kw)
    with pytest.raises(FileExistsError):
        cc.create_single_config(**kw)
    cc.create_single_config(**kw, exist_ok=True)


def test_create_config_cli(tmp_path):
    rc = cc.main(["--out_dir", str(tmp_path), "--exp_name", "cli_exp",
                  "--model_name", "HuggingFaceTB/SmolLM-135M",
                  "--dp", "1", "--seq_len", "256", "--use_cpu"])
    assert rc == 0
    cfg = Config.from_json(str(tmp_path / "cli_exp" / "config.json"))
    assert cfg.model.num_hidden_layers == 30


# ---------------------------------------------------------------- status triage


def test_classify_log_patterns():
    assert sj.classify_log("... RESOURCE_EXHAUSTED: out of memory ...", 1) is sj.Status.OOM
    assert sj.classify_log("xx DUE TO TIME LIMIT xx", None) is sj.Status.TIMEOUT
    assert sj.classify_log("Traceback ...", 1) is sj.Status.FAIL
    assert sj.classify_log("done: 2 steps", 0) is sj.Status.COMPLETED
    # exit code wins over benign warning substrings in successful runs
    assert sj.classify_log(
        "W0001 Attempting to reserve 2.1G\ndone: 100 steps", 0) is sj.Status.COMPLETED
    assert sj.classify_log(
        "Timed out waiting for barrier, retrying\ndone", 0) is sj.Status.COMPLETED


def test_job_status_roundtrip(tmp_path):
    job = sj.Job(str(tmp_path))
    assert job.status is sj.Status.INIT  # no status.txt yet
    job.set_status(sj.Status.PENDING)
    assert sj.Job(str(tmp_path)).status is sj.Status.PENDING


def _make_tiny_exp(tmp_path, name, steps=2):
    raw = {
        "distributed": {"use_cpu": True},
        "model": dict(num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, hidden_size=32,
                      intermediate_size=64, vocab_size=128,
                      max_position_embeddings=64, dtype="float32",
                      attention_impl="sdpa"),
        "training": dict(seq_length=32, micro_batch_size=2,
                         total_train_steps=steps, remat="none"),
        "dataset": {"name": "synthetic"},
    }
    d = tmp_path / name
    d.mkdir(parents=True)
    with open(d / "config.json", "w") as f:
        json.dump(raw, f)
    return d


def test_scheduler_local_end_to_end(tmp_path):
    _make_tiny_exp(tmp_path, "run_dp1_tp1_mbs2_sl32")
    sched = sj.Scheduler(str(tmp_path), backend="local")
    assert len(sched.jobs) == 1
    job = sched.jobs[0]
    status = sched.run_local(job, timeout_s=600)
    log = open(job.log_path).read()
    assert status is sj.Status.COMPLETED, log
    assert "Step:" in log
    # resubmit filter: completed jobs are not selected by default
    assert sched.select(None) == []
    assert sched.select("completed") == [job]


def test_scheduler_classifies_failure(tmp_path):
    d = _make_tiny_exp(tmp_path, "broken")
    # corrupt the config so the run fails fast
    with open(d / "config.json", "w") as f:
        f.write("{not json")
    sched = sj.Scheduler(str(tmp_path), backend="local")
    status = sched.run_local(sched.jobs[0], timeout_s=120)
    assert status is sj.Status.FAIL


def test_slurm_render(tmp_path):
    _make_tiny_exp(tmp_path, "slurm_exp")
    sched = sj.Scheduler(str(tmp_path), backend="slurm")
    script = sched.render_slurm(sched.jobs[0])
    text = open(script).read()
    assert "picotron_tpu.train" in text
    assert "status.txt" in text
    assert "{{" not in text  # fully rendered


# ------------------------------------------------------------- extract_metrics


SAMPLE_LOG = """\
model SmolLM: 1.71B params | mesh dp=1 pp=1 cp=1 tp=1 on 1 x TPU v5e
Step: 1     | Loss: 10.8016 | Global batch size: 8.19K | Tokens/s: 1.02K | Tokens/s/chip: 1.02K | Tokens: 8.19K | MFU: 1.00% | Memory usage: 4.10GB
Step: 2     | Loss: 9.5000 | Global batch size: 8.19K | Tokens/s: 30.00K | Tokens/s/chip: 30.00K | Tokens: 16.38K | MFU: 30.00% | Memory usage: 4.10GB
Step: 3     | Loss: 9.0000 | Global batch size: 8.19K | Tokens/s: 31.00K | Tokens/s/chip: 31.00K | Tokens: 24.58K | MFU: 31.00% | Memory usage: 4.10GB
Step: 4     | Loss: 8.5000 | Global batch size: 8.19K | Tokens/s: 40.00K | Tokens/s/chip: 40.00K | Tokens: 32.77K | MFU: 40.00% | Memory usage: 4.10GB
Step: 5     | Loss: 8.0000 | Global batch size: 8.19K | Tokens/s: 42.00K | Tokens/s/chip: 42.00K | Tokens: 40.96K | MFU: 42.00% | Memory usage: 4.10GB
done: 5 steps
"""


def test_parse_log_line():
    row = em.parse_log_line(SAMPLE_LOG.splitlines()[1])
    assert row == {
        "step": 1, "loss": 10.8016, "tokens_per_sec": 1020.0,
        "tokens_per_sec_per_chip": 1020.0, "mfu_pct": 1.0, "memory_gb": 4.10,
    }
    assert em.parse_log_line("model SmolLM: 1.71B params") is None


def test_extract_sweep(tmp_path):
    run = tmp_path / "smollm_dp2_tp4_pp1_cp1_mbs1_ga8_sl2048"
    run.mkdir()
    (run / "log.out").write_text(SAMPLE_LOG)
    rows = em.extract(str(tmp_path))
    assert len(rows) == 1
    r = rows[0]
    # warmup: first 3 steps dropped -> mean of steps 4,5
    assert r["num_steps"] == 2
    assert r["tokens_per_sec_per_chip"] == pytest.approx(41000.0)
    assert r["mfu_pct"] == pytest.approx(41.0)
    assert r["final_loss"] == pytest.approx(8.0)
    assert (r["dp"], r["tp"], r["pp"], r["cp"]) == (2, 4, 1, 1)
    assert (r["micro_batch_size"], r["grad_acc"], r["seq_len"]) == (1, 8, 2048)
    assert (run / "metrics.csv").exists()
    assert (tmp_path / "global_metrics.csv").exists()


def test_from_readable_format():
    assert em.from_readable_format("1.5K") == 1500.0
    assert em.from_readable_format("2M") == 2_000_000.0
    assert em.from_readable_format("7") == 7.0


# ----------------------------------------------- metrics JSONL (obs) source


def _jsonl_row(step, loss, tps):
    import json

    return json.dumps({"step": step, "loss": loss, "tokens_per_sec": tps,
                       "tokens_per_sec_per_chip": tps, "trained_tokens": 1,
                       "mfu_pct": None, "memory_gb": None, "t": 0.0})


def test_parse_jsonl_file_rows_and_junk(tmp_path):
    """Step rows come back in parse_log_file's shape; the summary row,
    corrupt lines, and a truncated tail (killed run) are skipped without
    losing the steps before them."""
    p = tmp_path / "metrics.jsonl"
    p.write_text(
        _jsonl_row(1, 10.5, 1000.0) + "\n"
        + "not json at all\n"
        + _jsonl_row(2, 9.5, 2000.0) + "\n"
        + '{"event": "summary", "metrics": {}}\n'
        + '{"step": 3, "loss": 9.0, "tokens_per_sec": 3000.0'  # truncated
    )
    rows = em.parse_jsonl_file(str(p))
    assert [r["step"] for r in rows] == [1, 2]
    assert rows[0]["loss"] == pytest.approx(10.5)
    assert rows[1]["tokens_per_sec_per_chip"] == pytest.approx(2000.0)
    assert rows[0]["mfu_pct"] is None and rows[0]["memory_gb"] is None


def test_extract_prefers_jsonl_over_log(tmp_path):
    """A run dir with BOTH sources: the structured JSONL wins and the
    disagreeing legacy log is never regex-scraped."""
    run = tmp_path / "smollm_dp2_tp4_pp1_cp1_mbs1_ga8_sl2048"
    run.mkdir()
    (run / "log.out").write_text(SAMPLE_LOG)  # says final_loss 8.0
    (run / em.JSONL_NAME).write_text(
        "\n".join(_jsonl_row(s, 20.0 - s, 5000.0) for s in range(1, 6))
        + "\n")
    rows = em.extract(str(tmp_path))
    assert len(rows) == 1
    assert rows[0]["final_loss"] == pytest.approx(15.0)  # JSONL, not 8.0
    assert rows[0]["tokens_per_sec_per_chip"] == pytest.approx(5000.0)
    assert (rows[0]["dp"], rows[0]["tp"]) == (2, 4)  # folder parse intact


def test_extract_falls_back_to_legacy_log(tmp_path):
    """An empty/corrupt JSONL (or none at all) drops to the regex path —
    pre-obs runs keep extracting exactly as before."""
    run = tmp_path / "smollm_dp1_tp1_pp1_cp1_mbs1_ga1_sl2048"
    run.mkdir()
    (run / "log.out").write_text(SAMPLE_LOG)
    (run / em.JSONL_NAME).write_text("garbage\n{\n")
    rows = em.extract(str(tmp_path))
    assert len(rows) == 1
    assert rows[0]["final_loss"] == pytest.approx(8.0)  # the log's numbers
    assert rows[0]["num_steps"] == 2


# ------------------------------------------------------------------- packaging


def test_root_shims_importable():
    """The repo-root shims must resolve against the package."""
    for shim in ("create_config.py", "submit_jobs.py", "extract_metrics.py"):
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), shim)
        assert os.path.exists(path)
    out = subprocess.run(
        [sys.executable, "-c",
         "from picotron_tpu.tools import create_config, submit_jobs, "
         "extract_metrics; print('ok')"],
        capture_output=True, text=True)
    assert out.stdout.strip() == "ok", out.stderr


def test_parse_folder_name_anchored():
    """Keys must not match inside other tokens (round-1 ADVICE: undelimited
    dp(\\d+) regexes mislabel sweep rows)."""
    from picotron_tpu.tools.extract_metrics import parse_folder_name

    got = parse_folder_name("smollm_dp2_tp4_pp2_cp1_mbs1_ga8_sl2048")
    assert (got["dp"], got["tp"], got["pp"], got["cp"]) == (2, 4, 2, 1)
    assert (got["micro_batch_size"], got["grad_acc"], got["seq_len"]) == (1, 8, 2048)
    # 'warmup3' must not read as pp=3; 'setup2' must not read as tp=2;
    # 'speedup9' must not poison anything
    got = parse_folder_name("warmup3_setup2_speedup9_dp4")
    assert got["dp"] == 4
    assert got["pp"] is None and got["tp"] is None
    # no topology tokens at all
    got = parse_folder_name("baseline_run")
    assert all(v is None for v in got.values())


def test_create_config_round3_flags(tmp_path):
    """cp_zigzag / remat / steps_per_call are reachable from the generator
    CLI surface."""
    from picotron_tpu.tools.create_config import main as cc_main

    rc = cc_main([
        "--out_dir", str(tmp_path), "--exp_name", "zig",
        "--model_name", "HuggingFaceTB/SmolLM-1.7B",
        "--cp", "2", "--cp_zigzag", "--remat", "save_attn",
        "--steps_per_call", "8", "--seq_len", "2048", "--use_cpu", "--dp", "4"])
    assert rc == 0
    cfg = json.load(open(tmp_path / "zig" / "config.json"))
    assert cfg["distributed"]["cp_zigzag"] is True
    assert cfg["training"]["remat"] == "save_attn"
    assert cfg["training"]["steps_per_call"] == 8


def test_create_config_sp_zero1_flags(tmp_path):
    """tp_sequence_parallel / zero1 are reachable from the generator CLI and
    default to the template's values when absent."""
    from picotron_tpu.tools.create_config import main as cc_main

    rc = cc_main([
        "--out_dir", str(tmp_path), "--exp_name", "spz",
        "--model_name", "HuggingFaceTB/SmolLM-1.7B",
        "--tp", "2", "--dp", "2", "--tp_sequence_parallel", "--zero1",
        "--seq_len", "2048", "--use_cpu"])
    assert rc == 0
    cfg = json.load(open(tmp_path / "spz" / "config.json"))
    assert cfg["distributed"]["tp_sequence_parallel"] is True
    assert cfg["distributed"]["zero1"] is True

    rc = cc_main([
        "--out_dir", str(tmp_path), "--exp_name", "plain",
        "--model_name", "HuggingFaceTB/SmolLM-1.7B", "--use_cpu"])
    assert rc == 0
    cfg = json.load(open(tmp_path / "plain" / "config.json"))
    assert cfg["distributed"]["tp_sequence_parallel"] is False
    assert cfg["distributed"]["zero1"] is False


# ---------------------------------------------------------- project_multichip


def test_projection_ladder_sane():
    """The multi-chip projection (docs/PROJECTION.md) must stay internally
    consistent: MFU below the single-chip anchor, every ladder config fitting
    v5e HBM, and the BASELINE north star (>= 40% SmolLM on v5e-16) holding
    under the stated conservative assumptions."""
    from picotron_tpu.tools import project_multichip as pm

    rows = [pm.project(lc) for lc in pm.LADDER]
    for lc, r in zip(pm.LADDER, rows):
        assert 0 < r["mfu"] < 100 * lc.model.eff_1chip
        # configs must fit v5e HBM unless explicitly tagged as over (the
        # canonical config-5 is shown alongside a fitting variant)
        assert r["mem_gb"] < 16.0 or "over HBM" in r["config"], (
            f"{r['config']} does not fit v5e HBM")
        assert r["comm_eff"] <= 100 and r["bubble_eff"] <= 100
    assert any(r["mem_gb"] < 16.0 and "seq8192" in r["config"]
               for r in rows), "no fitting 7B long-context config"
    north_star = next(r for r in rows if "cp2" in r["config"]
                      and "SmolLM" in r["config"])
    assert north_star["mfu"] >= 40.0


def test_projection_param_count_matches_model():
    """The projector's closed-form n_params must agree with the real model's
    count (llama.num_params) for both ladder models."""
    from picotron_tpu.config import SMOLLM_1_7B, ModelConfig
    from picotron_tpu.models import llama
    from picotron_tpu.tools import project_multichip as pm

    mc = ModelConfig(**SMOLLM_1_7B)
    assert pm.SMOLLM.n_params() == llama.num_params(mc)


def test_measure_cond_gating_small(capsys):
    """The cond-gating micro-bench (VERDICT r3 weak #3) runs end-to-end on
    the CPU mesh and reports every field the round record needs. The
    TPU-magnitude claim itself (gated-false ~ free) is only checkable on
    hardware."""
    from picotron_tpu.tools import measure_cond_gating as mcg

    rc = mcg.main(["--small"])
    assert rc == 0
    out = capsys.readouterr().out
    rec = json.loads([l for l in out.splitlines() if l.startswith("{")][-1])
    for k in ("loss_owner_ms", "loss_gated_other_ms",
              "loss_maskedboth_other_ms", "embed_owner_ms",
              "embed_gated_other_ms", "embed_maskedboth_other_ms"):
        assert rec[k] > 0


@pytest.mark.slow
def test_measure_offload_bw_small(capsys):
    """The offload-economics probe (remat='offload' bandwidth math) runs end-to-end on CPU and reports link bandwidth +
    both step timings; the decisive PCIe numbers need the chip."""
    from picotron_tpu.tools import measure_offload_bw as mob

    rc = mob.main(["--small"])
    assert rc == 0
    out = capsys.readouterr().out
    rec = json.loads([l for l in out.splitlines() if l.startswith("{")][-1])
    assert rec["d2h_gbps"] > 0 and rec["h2d_gbps"] > 0
    assert rec["save_attn_ms"] > 0 and rec["offload_ms"] > 0
    assert rec["value"] > 0
