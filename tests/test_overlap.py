"""Zero-bubble overlapped scheduling (inference.overlap,
docs/INFERENCE.md "Overlapped scheduling").

The tentpole gate is BIT-IDENTITY: with the per-slot key schedule, the
two-stage pipeline (issue round N+1 before syncing round N) must emit
exactly the streams the serial scheduler emits — greedy AND seeded
stochastic — across the engine matrix (decode_block/verify/chunked x
dense/flash x contiguous/paged x int8 x tp x dp). Around it:

- the key-schedule invariant itself: a slot-keyed stream depends only on
  (base key, position), so it is independent of round structure — block
  length, speculative grouping — and, for greedy, of the schedule;
- late-stop rollback: a round issued against stale budgets/EOS state
  overshoots on device, and the sync stage's masked delivery plus the
  length-pointer discipline emit every token exactly once;
- composition: slot-isolation re-dispatch, ServingChaos faults, and the
  dp=2 rebalance planner all run UNDER the pipeline with the same
  accounting and exactness contracts they have without it;
- drain: `busy` covers the in-flight lookahead round, so a drain loop
  flushes it instead of stranding its tokens.

What the pipeline does to tokens/s has not been measured on the chip: no
benchmark cell turns ``overlap`` on. What the dispatch gap counts in either
mode is pinned below on a manual clock.
"""

from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from conftest import make_config
from picotron_tpu.inference import (
    ContinuousBatcher,
    InferenceEngine,
    Request,
)
from picotron_tpu.models import llama
from picotron_tpu.obs import MetricsRegistry, Obs
from picotron_tpu.resilience.chaos import ServingChaos

MAX_LEN = 96


def _engine(tiny_model_kwargs, overlap, tp=1, dp=1, slots=4,
            key_schedule="slot", hooks=None, **kw):
    cfg = make_config(tiny_model_kwargs, tp=tp, seq=MAX_LEN)
    cfg.inference.dp_size = dp
    kw.setdefault("decode_block_len", 4)
    eng = InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN,
                          overlap=overlap, key_schedule=key_schedule,
                          hooks=hooks, **kw)
    return cfg, eng


def _params(cfg, engine, seed=0):
    p = jax.jit(lambda k: llama.init_params(k, cfg.model))(
        jax.random.PRNGKey(seed))
    if engine.quant_weights:
        p = llama.quantize_params(p)
    return engine.shard_params(p)


def _reqs(program, temp=0.0):
    """Mixed-length batch; ``verify`` uses repetitive prompts (the regime
    prompt-lookup drafting accepts on), ``chunked`` prompts span several
    prefill chunks. Lengths deliberately retire at different rounds so
    the pipeline crosses admissions, finishes, and partial occupancy."""
    k = dict(temperature=temp, top_k=0 if temp == 0 else 40, top_p=0.95)
    if program == "verify":
        return [Request("a", [5, 9, 5, 9, 5, 9], max_new_tokens=18, **k),
                Request("b", [7, 3, 7, 3, 7], max_new_tokens=11, **k),
                Request("c", [11, 12, 11, 12], max_new_tokens=4, **k)]
    if program == "chunked":
        long_a = [(5 * i + 2) % 199 + 1 for i in range(20)]
        long_b = [(3 * i + 7) % 199 + 1 for i in range(17)]
        return [Request("a", long_a, max_new_tokens=14, **k),
                Request("b", long_b, max_new_tokens=10, **k),
                Request("c", [11, 12] * 5, max_new_tokens=4, **k)]
    return [Request("a", [5, 9, 5, 9, 5, 9], max_new_tokens=19, **k),
            Request("b", [7, 3, 7, 3, 7], max_new_tokens=13, **k),
            Request("c", [11, 12, 11, 12], max_new_tokens=4, **k)]


def _run(tiny_model_kwargs, overlap, program="block", temp=0.0, seed=7,
         **kw):
    if program == "verify":
        kw.setdefault("spec_len", 3)
    if program == "chunked":
        kw.setdefault("prefill_chunk", 8)
    cfg, eng = _engine(tiny_model_kwargs, overlap, **kw)
    b = ContinuousBatcher(eng, _params(cfg, eng), seed=seed)
    res = b.run(_reqs(program, temp))
    return {u: (r.tokens, r.finish_reason) for u, r in res.items()}, b


# --------------------------------------------------------------------------- #
# the tentpole: overlap-on == overlap-off across the engine matrix
# --------------------------------------------------------------------------- #


# The full matrix is the gate; the un-marked legs are the tier-1 core and
# the rest ride the `slow` lane (same budget discipline as the sharded
# and speculative matrices).
_slow = pytest.mark.slow
@pytest.mark.parametrize("program,layout,attend,quant,tp,dp,temp", [
    ("block",   "contiguous", "dense", None,     1, 1, 0.0),
    ("block",   "contiguous", "dense", None,     1, 1, 0.9),
    pytest.param("block", "paged", "dense", None,     1, 1, 0.9, marks=_slow),
    pytest.param("block", "paged", "flash", None,     1, 1, 0.0, marks=_slow),
    pytest.param("block", "contiguous", "dense", "int8kv", 1, 1, 0.9,
                 marks=_slow),
    pytest.param("block", "paged", "dense", "int8w",  1, 1, 0.0, marks=_slow),
    pytest.param("block", "contiguous", "dense", None, 2, 1, 0.9,
                 marks=_slow),
    pytest.param("block", "paged", "dense", None,     1, 2, 0.9, marks=_slow),
    pytest.param("verify", "contiguous", "dense", None, 1, 1, 0.0,
                 marks=_slow),
    ("verify",  "contiguous", "dense", None,     1, 1, 0.9),
    pytest.param("verify", "paged", "dense", None,    1, 2, 0.0, marks=_slow),
    ("chunked", "paged",      "dense", None,     1, 1, 0.0),
])
def test_overlap_identity_matrix(tiny_model_kwargs, program, layout,
                                 attend, quant, tp, dp, temp):
    """Overlap-on emits streams BIT-IDENTICAL to overlap-off — same seed,
    same per-slot key schedule — for every program family crossed with
    representative kernel/layout/quantization corners, greedy and seeded
    stochastic, on tp=2 and dp=2. This is the whole correctness story:
    the pipeline may overshoot on device and deliver a round late, but
    nothing observable moves."""
    kw = dict(kv_layout=layout, attend_impl=attend, tp=tp, dp=dp)
    if quant == "int8kv":
        kw["cache_dtype"] = "int8"
    elif quant == "int8w":
        kw["weight_dtype"] = "int8"
    off, _ = _run(tiny_model_kwargs, False, program, temp, **kw)
    on, b = _run(tiny_model_kwargs, True, program, temp, **kw)
    assert on == off, (program, layout, attend, quant, tp, dp, temp)
    st = b.stats()
    assert st["overlap"]["enabled"]
    assert b._inflight is None  # drained, nothing stranded


@pytest.mark.slow
def test_slot_schedule_greedy_matches_round_schedule(tiny_model_kwargs):
    """Greedy decode is key-independent, so the slot schedule (overlap's
    prerequisite) changes nothing against the legacy round schedule —
    the default-off path and the overlap path share one greedy oracle."""
    legacy, _ = _run(tiny_model_kwargs, False, key_schedule="round")
    slot, _ = _run(tiny_model_kwargs, False, key_schedule="slot")
    assert legacy == slot


@pytest.mark.slow
def test_slot_stream_independent_of_round_structure(tiny_model_kwargs):
    """The key-schedule invariant: token at position p is keyed
    fold_in(base, p - 1) no matter how rounds chunk the stream — so a
    seeded-stochastic stream is identical across decode block lengths
    AND under speculative grouping (sample-and-match draws the same
    chain), which is exactly why one-round-stale drafts and overshot
    rounds cannot perturb emitted tokens."""
    b2, _ = _run(tiny_model_kwargs, False, temp=0.9, decode_block_len=2)
    b4, _ = _run(tiny_model_kwargs, False, temp=0.9, decode_block_len=4)
    spec, _ = _run(tiny_model_kwargs, False, temp=0.9, spec_len=3,
                   decode_block_len=1)
    assert b2 == b4
    assert spec == b4


def test_overlap_rejects_round_key_schedule(tiny_model_kwargs):
    """overlap + key_schedule='round' is an invalid combination (a
    round-shared key makes streams depend on stale round membership):
    config.validate and the engine both refuse it."""
    cfg = make_config(tiny_model_kwargs, seq=MAX_LEN)
    cfg.inference.overlap = True
    cfg.inference.key_schedule = "round"
    with pytest.raises(ValueError, match="key schedule"):
        cfg.validate()
    cfg2 = make_config(tiny_model_kwargs, seq=MAX_LEN)
    with pytest.raises(ValueError, match="key schedule"):
        InferenceEngine(cfg2, slots=2, max_seq_len=MAX_LEN,
                        overlap=True, key_schedule="round")


# --------------------------------------------------------------------------- #
# late-stop rollback: overshot rounds deliver exactly once
# --------------------------------------------------------------------------- #


def test_late_stop_budget_rollback_exactly_once(tiny_model_kwargs):
    """max_new_tokens that end mid-round: the lookahead round was issued
    against a stale budget and the device overshoots, but the sync
    stage's host walk truncates at the request's own limit — stream
    lengths are exact, nothing duplicated, nothing dropped."""
    for temp in (0.0, 0.9):
        on, b = _run(tiny_model_kwargs, True, temp=temp)
        want = {"a": 19, "b": 13, "c": 4}  # none a multiple of block 4
        for uid, n in want.items():
            toks, reason = on[uid]
            assert len(toks) == n, (uid, temp)
            assert reason == "length"
        assert b.counters["completed"] == 3


def test_late_eos_rollback_exactly_once(tiny_model_kwargs):
    """An EOS that lands mid-round while the NEXT round is already in
    flight: the on-device stop state masks the late-finished slot in the
    overshot round (counts merge), the host walk cuts at EOS, and the
    stream equals the serial scheduler's to the last token."""
    base, _ = _run(tiny_model_kwargs, False)
    # pick an eos the greedy stream actually emits mid-round for "a"
    eos = base["a"][0][5]
    reqs_kw = dict(eos_id=eos, max_new_tokens=19)

    def run(overlap):
        cfg, eng = _engine(tiny_model_kwargs, overlap)
        b = ContinuousBatcher(eng, _params(cfg, eng), seed=7)
        res = b.run([Request("a", [5, 9, 5, 9, 5, 9], **reqs_kw),
                     Request("b", [7, 3, 7, 3, 7], max_new_tokens=13),
                     Request("c", [11, 12, 11, 12], max_new_tokens=4)])
        return {u: (r.tokens, r.finish_reason) for u, r in res.items()}

    off, on = run(False), run(True)
    assert on == off
    assert on["a"][1] == "eos"
    assert on["a"][0][-1] == eos
    assert eos not in on["a"][0][:-1]  # exactly once, nothing replayed


# --------------------------------------------------------------------------- #
# composition: isolation re-dispatch, chaos, dp rebalance, drain
# --------------------------------------------------------------------------- #


@pytest.mark.slow
def test_overlap_slot_isolation_redispatch(tiny_model_kwargs):
    """A persistently failing slot under the pipeline: the fallback
    serial round isolates it (finishes "error"), SURVIVORS' streams are
    bit-identical to the fault-free overlap run — greedy and sampled
    rows — and no slot, queue entry, or in-flight record leaks."""
    clean, _ = _run(tiny_model_kwargs, True, temp=0.9)
    chaos = ServingChaos(_chaos_res(tiny_model_kwargs,
                                    chaos_dispatch_fail_slot=1))
    on, b = _run(tiny_model_kwargs, True, temp=0.9, hooks=chaos)
    # "b" was admitted into the faulted slot: errors with only its
    # prefill-time first token (identical to the clean run's)
    assert on["b"][1] == "error"
    assert on["b"][0] == clean["b"][0][:1]
    for uid in ("a", "c"):
        assert on[uid] == clean[uid]
    assert all(s is None for s in b._slots)
    assert b._inflight is None
    assert b.queue_depth == 0
    assert b.counters["errored"] == 1
    assert b.counters["completed"] == 2


def _chaos_res(tiny_model_kwargs, **kw):
    cfg = make_config(tiny_model_kwargs, seq=MAX_LEN)
    for k, v in kw.items():
        setattr(cfg.resilience, k, v)
    cfg.validate()
    return cfg.resilience


@pytest.mark.slow
def test_overlap_chaos_faults_account_everything(tiny_model_kwargs):
    """Transient dispatch exception + latency spike + poisoned logits,
    all inside the pipeline: no hang, every request terminates with an
    accounted finish_reason, emitted tokens stay defined, and the
    transient fault is absorbed bit-identically (the fallback replays
    the SAME slot-keyed draws, so retries cannot fork a stream)."""
    clean, _ = _run(tiny_model_kwargs, True, temp=0.9)
    chaos = ServingChaos(_chaos_res(
        tiny_model_kwargs, chaos_dispatch_raise_round=2,
        chaos_latency_round=3, chaos_latency_s=0.05,
        chaos_poison_logits_round=4))
    on, b = _run(tiny_model_kwargs, True, temp=0.9, hooks=chaos)
    assert chaos._fired >= {"raise", "latency", "poison"}
    vocab = 256
    for uid, (toks, reason) in on.items():
        assert reason in ("length", "eos")
        assert all(0 <= t < vocab for t in toks)
    # the raise round is absorbed by the serial fallback; the poison
    # round changes sampled VALUES (that is its job) but never counts
    assert b.counters["errored"] == 0
    assert b.counters["completed"] == 3
    assert {u: len(t) for u, (t, _) in on.items()} == \
        {u: len(t) for u, (t, _) in clean.items()}


@pytest.mark.slow
def test_overlap_dp2_rebalance_streams_exact(tiny_model_kwargs):
    """The dp=2 paged skewed workload under the pipeline: short streams
    retire early, the occupancy watermark trips, and the planner drains
    the in-flight round before migrating (migrate_slot reads host
    lengths the lookahead round would otherwise leave stale) — streams
    still equal the dp=1 overlap run and the migration counters moved."""
    reqs = [Request("l0", [1, 2, 3, 4, 5], max_new_tokens=24),
            Request("l1", [9, 8, 7, 6], max_new_tokens=24),
            Request("s0", [11, 12], max_new_tokens=4),
            Request("s1", [13, 14, 15], max_new_tokens=4)]

    def run(dp):
        cfg, eng = _engine(tiny_model_kwargs, True, dp=dp,
                           kv_layout="paged")
        b = ContinuousBatcher(eng, _params(cfg, eng), seed=7)
        res = b.run([Request(**vars(r)) for r in reqs])
        return {u: (r.tokens, r.finish_reason) for u, r in res.items()}, b

    base, _ = run(1)
    got, b2 = run(2)
    assert got == base
    st = b2.stats()
    assert st["rebalance_count"] >= 1
    assert st["rebalance_bytes"] > 0


def test_drain_flushes_inflight_lookahead_round(tiny_model_kwargs):
    """`busy` covers the in-flight record, so serve.py's drain loop
    (`while busy: step()`) flushes the lookahead round instead of
    stranding its tokens: stepping manually, the batcher stays busy
    while ONLY the in-flight round remains, and the flushed streams are
    complete to the exact token count."""
    cfg, eng = _engine(tiny_model_kwargs, True)
    b = ContinuousBatcher(eng, _params(cfg, eng), seed=7)
    for r in _reqs("block"):
        b.submit(r)
    saw_inflight_only = False
    steps = 0
    while b.busy:
        b.step()
        steps += 1
        if b._inflight is not None and b.queue_depth == 0:
            saw_inflight_only = True
        assert steps < 200, "drain loop did not terminate"
    assert saw_inflight_only  # the pipeline actually ran a lookahead
    assert b._inflight is None
    res = b.take_results()
    assert {u: len(r.tokens) for u, r in res.items()} == \
        {"a": 19, "b": 13, "c": 4}


@pytest.mark.parametrize("overlap", [False, True])
def test_dispatch_gap_is_host_time_from_sync_end_to_issue(
        tiny_model_kwargs, overlap, monkeypatch):
    """What ``picotron_dispatch_gap_seconds`` counts, on a clock where only
    the hand-over of a slot's tokens costs time (1 ms): the serial step
    pays it between one round's sync end and the next issue, so every gap
    is at least a millisecond; the pipelined step issues with a round in
    flight, so every gap is 0.0."""
    cfg, eng = _engine(tiny_model_kwargs, overlap)
    eng.obs = Obs(enabled=True, registry=MetricsRegistry())
    now = [100.0]
    b = ContinuousBatcher(eng, _params(cfg, eng), seed=7,
                          clock=lambda: now[0])
    inner = b._tokens_done

    def costing(*a, **kw):
        now[0] += 1e-3
        return inner(*a, **kw)

    monkeypatch.setattr(b, "_tokens_done", costing)
    b.run(_reqs("block"))
    gaps = b._gap_hist.read()
    assert gaps["count"] == b.decode_dispatches - 1 >= 3  # none at the first
    p50 = b.stats()["overlap"]["dispatch_gap_s"]["p50"]
    if overlap:
        assert gaps["sum"] == 0.0 and p50 == 0.0
    else:
        assert gaps["sum"] >= gaps["count"] * 1e-3 and p50 >= 1e-3


def test_stats_overlap_payload_and_threaded_scrape(tiny_model_kwargs):
    """stats() exposes the overlap A/B payload and takes its scratch
    snapshots (last_host_sync_s, last_prefill) under the leaf lock — a
    scrape hammering from another thread mid-run sees consistent values
    and never trips the pipeline (the C003/C004 fixture in
    tests/test_analysis.py pins the lock discipline statically)."""
    cfg, eng = _engine(tiny_model_kwargs, True)
    b = ContinuousBatcher(eng, _params(cfg, eng), seed=7)
    stop = threading.Event()
    seen = []

    def scrape():
        while not stop.is_set():
            st = b.stats()
            assert st["overlap"]["enabled"] is True
            seen.append(st.get("last_host_sync_s"))

    t = threading.Thread(target=scrape, daemon=True)
    t.start()
    try:
        b.run(_reqs("block"))
    finally:
        stop.set()
        t.join(timeout=5)
    st = b.stats()
    assert "last_prefill" in st and "last_host_sync_s" in st
    ov = st["overlap"]
    assert ov["enabled"] is True
    assert ov["dispatch_gap_s"] is None or "p50" in ov["dispatch_gap_s"]
    assert 0.0 <= ov.get("overlap_efficiency", 0.0) <= 1.0
