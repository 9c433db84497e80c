"""The harness end to end at toy size on the CPU (the rehearsal switch) for
one train and one serve cell: the final line's keys, and no device metric
printed under a CPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(cell, trace, extra=()):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "3000000001", "--seconds", "2", "--trace", str(trace), *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    return p


@pytest.mark.parametrize("cell,trace,computed", [
    ("smollm-1.7b.train-2k", 0, {"train_tokens_per_s_chip", "setup_s"}),
    ("smollm-1.7b.train-2k", 1, {"train_step.step_ms"}),
    ("smollm-1.7b.serve-batch", 0,
     {"serve_out_tokens_per_s", "serve_itl_p99_ms", "setup_s"}),
    ("smollm-1.7b.serve-batch", 1, {"batcher.dispatch_gap_ms"}),
    # --trace 2: the --trace 0 run, then a traced tail; both kinds of metric
    ("smollm-1.7b.train-2k", 2,
     {"train_tokens_per_s_chip", "setup_s", "train_step.step_ms"}),
    ("smollm-1.7b.serve-batch", 2,
     {"serve_out_tokens_per_s", "serve_itl_p99_ms", "setup_s",
      "batcher.dispatch_gap_ms", "front.loop_lock_wait_ms",
      "front.results_ms", "batcher.plan_ms", "batcher.deliver_ms"}),
    ("mistral-7b-v0.3-l16.serve-chat", 2,
     {"serve_tpot_mean_ms", "setup_s", "batcher.dispatch_gap_ms.chat",
      "front.loop_lock_wait_ms.chat", "front.results_ms.chat",
      "batcher.plan_ms.chat", "batcher.deliver_ms.chat",
      "batcher.admit_ms.chat", "engine.prefill_tokens_per_s.chat",
      "engine.prompt_reuse_pct.chat"}),
    # documents asked more than once, by clients that wait
    ("mistral-7b-v0.3-l16.serve-longdoc", 2,
     {"serve_out_tokens_per_s.longdoc", "serve_tpot_mean_ms", "setup_s",
      "batcher.admit_ms.chat", "batcher.itl_p99_ms.chat",
      "engine.prefill_tokens_per_s.chat", "engine.prompt_reuse_pct.chat"}),
])
def test_rehearsal_final_line(cell, trace, computed):
    p = run_cell(cell, trace, ["--rehearse"])
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert KEYS <= set(out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0 and out["compiles_in_window"] == 0
    # a CPU run names the CPU and prints no number under a metric's name
    assert out["device"]["platform"] == "cpu"
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert "busy_s" not in out["device"] and "breakdown" not in out
    assert computed <= set(out["computed"])
    if trace == 0:  # the end-to-end metrics and nothing else
        assert computed == set(out["computed"])


def test_without_the_switch_a_cpu_is_refused():
    p = run_cell("smollm-1.7b.train-2k", 0)
    assert p.returncode == 2
    assert not p.stdout.strip()
