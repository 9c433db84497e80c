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
         "front.results_ms",
         # the stall judge's counters print at 0 from the server's start
         "batcher.stall_s", "engine.device_wait_stall_s",
         "front.oversleep_s"}
CHAT = {r + ".chat" for r in ROUND} | {
    "batcher.admit_ms.chat", "batcher.itl_p99_ms.chat",
    "engine.prefill_tokens_per_s.chat", "engine.prompt_reuse_pct.chat",
    "engine.prefill_pad_rows_pct.chat"}
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


def _toy_reuse_pct(cell: str) -> float:
    """What ``engine.prompt_reuse_pct.chat`` computes, at toy size in this
    process: the mix's rehearsal schedule (the load generator's own plan:
    ``loadgen.build_schedule``) through a batcher over the configuration's
    rehearsal engine, as many requests in flight as it has slots; 100 x (1
    - ``picotron_prefill_tokens_total`` / the prompt tokens asked). A
    rehearsal prints no value, so the share is held here."""
    import jax

    from benchmarks import common, loadgen
    from benchmarks.run import merged
    from picotron_tpu.config import Config
    from picotron_tpu.inference import (ContinuousBatcher, InferenceEngine,
                                        Request)
    from picotron_tpu.models import llama

    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           entry["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           entry["traffic"] + ".json")) as f:
        traffic = json.load(f)
    config = merged(config, config["rehearsal"])
    traffic = merged(traffic, traffic["rehearsal"])
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True},
        "model": common.model_section(config),
        "training": {"seq_length": config["serve"]["max_seq_len"]},
        "dataset": {"name": "synthetic"}})
    # chunks of 32 stand to the toy prompts (44-90) as chunks of 512 to
    # the cell's (1,040-1,900): two or three a prompt, one a resumed suffix
    engine = InferenceEngine(cfg, prefill_chunk=32, **config["serve"])
    params = engine.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    plan = loadgen.build_schedule(traffic, 3000000001, 2.0,
                                  cfg.model.vocab_size)[:24]
    b = ContinuousBatcher(engine, params, seed=0)
    res = b.run([Request(uid=f"r{i}", prompt=r["prompt"],
                         max_new_tokens=r["max_new_tokens"])
                 for i, r in enumerate(plan)])
    assert all(len(res[f"r{i}"].tokens) == r["max_new_tokens"]
               for i, r in enumerate(plan))
    ran = b.obs.registry.counter("picotron_prefill_tokens_total").value
    return 100.0 * (1.0 - ran / sum(len(r["prompt"]) for r in plan))


def test_documents_asked_again_are_not_prefilled_again():
    """``serve-longdoc``'s plan at toy size (documents of 40-80 tokens
    asked three times, pages of 16): asks two and three find the
    document's whole pages retained, so a change that silently stops
    retaining fails here and not in a chip run."""
    assert _toy_reuse_pct("mistral-7b-v0.3-l16.serve-longdoc") > 25.0


def test_prompts_asked_once_are_prefilled_whole():
    """``serve-chat`` sends every prompt once: the store finds nothing."""
    assert _toy_reuse_pct("mistral-7b-v0.3-l16.serve-chat") == 0.0
