"""The Llama-block cells of ``BENCHMARK.json``, rehearsed: the command the
driver runs on the chip, walked at toy size on the CPU (``--rehearse``), so
that a change that breaks a cell's control flow, its correctness check or one
of its span and counter readers fails here and not after the PR is handed in.

The cells of the other blocks are rehearsed beside their blocks
(tests/test_deepseek_v32.py, test_granite_hybrid.py, test_minicpm_sala.py,
test_afmoe.py, test_mimo_v2.py); every other cell of the manifest is
rehearsed here, so the next cell on the Llama block needs no edit.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
REHEARSED_BESIDE_THEIR_BLOCK = {
    "deepseek-v3.2-ep32-l7.serve-longctx-decode",
    "granite-4.0-h-small-ep2-l10.serve-chat-closed",
    "minicpm-sala-l12.serve-longctx-decode",
    "trinity-large-ep32-l9.serve-mixedctx-decode",
    "mimo-v2.5-ep32-l13.serve-mixedctx-decode",
}
CELLS = [w["name"] for w in MANIFEST["workloads"]
         if w["name"] not in REHEARSED_BESIDE_THEIR_BLOCK]

# The per-layer readers a rehearsal fills: those of the program's own spans,
# counters and host clock. The device-trace readers (idle share, step time,
# rooflines, the collectives' share) and the MFU (a chip's peak) need a chip.
ROUND = {"batcher.plan_ms", "batcher.deliver_ms", "engine.issue_operands_ms",
         "engine.issue_enqueue_ms", "engine.sync_wait_ms",
         "engine.sync_fetch_ms", "front.loop_lock_wait_ms",
         "front.results_ms"}
CHAT = {r + ".chat" for r in ROUND} | {
    "batcher.admit_ms.chat", "batcher.itl_p99_ms.chat",
    "engine.prefill_tokens_per_s.chat", "engine.prompt_reuse_pct.chat"}
READERS = {
    "smollm-1.7b.train-2k": {"train_step.step_ms"},
    "smollm-1.7b.serve-batch": ROUND | {"batcher.dispatch_gap_ms"},
    "mistral-7b-v0.3-l16.serve-chat": CHAT | {"batcher.dispatch_gap_ms.chat"},
    "mistral-7b-v0.3-l16.train-pp2tp2": {"train_step.step_ms"},
    "mistral-7b-v0.3-l16.serve-longdoc": CHAT,
}


def of_cell(entries, cell):
    """The metrics of one manifest list that this cell reports (an entry
    that names no workloads, ``setup_s``, is every cell's)."""
    return {e["name"] for e in entries if cell in e.get("workloads", [cell])}


def counter_readers(cell):
    """What the manifest says reads the program's own counters in this cell."""
    return {e["name"] for e in MANIFEST["per_layer"]
            if cell in e["workloads"] and e["source"] == "program_counter"}


def test_the_manifest_has_the_five_llama_cells_and_their_counter_readers():
    assert set(READERS) <= set(CELLS)
    for cell, readers in READERS.items():
        assert counter_readers(cell) <= readers <= of_cell(
            MANIFEST["per_layer"], cell), cell


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_the_cell_computes_its_readers(cell):
    """The cell's control flow at toy size on the CPU (``train-pp2tp2`` on
    four virtual devices, which ``run.py`` asks for). The window is 3 s: the
    toy mixes turn a request round in tens of milliseconds, so rounds,
    prefills and re-asked documents fall inside it on a loaded machine."""
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         "3000000001", "--seconds", "3", "--trace", "2", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    chips = next(w["chips"] for w in MANIFEST["workloads"]
                 if w["name"] == cell)
    assert out["device"]["count"] >= chips  # the test workers' own eight
    want = of_cell(MANIFEST["end_to_end"], cell) \
        | READERS.get(cell, counter_readers(cell))
    assert want <= set(out["computed"]), want - set(out["computed"])
