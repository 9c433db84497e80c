"""Mixed prefill–decode dispatch (inference.mixed_dispatch,
docs/INFERENCE.md "Mixed prefill–decode dispatch").

The tentpole gate is BIT-IDENTITY: the fused program family — every
decode slot advances one step AND one fixed-width prefill lane per dp
shard in the SAME jitted call — must emit exactly the streams the
serial scheduler (separate prefill dispatches) emits, greedy AND seeded
stochastic, across the engine matrix (decode_block/verify x dense/flash
x contiguous/paged x int8 x tp x dp), with overlap composed on top.
Both sides run the slot key schedule (the lane's prerequisite, same as
overlap's): a slot-keyed stream depends only on (base key, position),
and the lane body is byte-for-byte the serial chunk program, so fusing
it into the decode dispatch cannot move a single bit. Around it:

- the scheduling contract: the lane is fed through ``_prefill_gate``'s
  token budget (the gate's round cap becomes the lane feed rate), its
  chunks count ``prefill_dispatches`` exactly like serial chunks, and
  ``picotron_prefill_lane_tokens_total`` /
  ``picotron_decode_stall_seconds`` make the interference story
  measurable;
- the gate itself (satellite): direct unit pins on the defer / preempt
  branches and their ``prefill_deferred`` / ``prefill_preempts``
  counter semantics, which the lane reuses verbatim;
- mixed_dispatch=False (default) leaves the serial path byte-identical
  — no lane state, no fused programs, lanes= rejected at the engine.

What the lane does to TPOT and TTFT has not been measured on the chip: no
benchmark cell turns ``mixed_dispatch`` on.
"""

from __future__ import annotations

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
from picotron_tpu.resilience.chaos import ServingChaos

MAX_LEN = 96


def _engine(tiny_model_kwargs, mixed, tp=1, dp=1, slots=4,
            key_schedule="slot", hooks=None, **kw):
    cfg = make_config(tiny_model_kwargs, tp=tp, seq=MAX_LEN)
    cfg.inference.dp_size = dp
    kw.setdefault("decode_block_len", 4)
    kw.setdefault("prefill_chunk", 8)
    eng = InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN,
                          mixed_dispatch=mixed, key_schedule=key_schedule,
                          hooks=hooks, **kw)
    return cfg, eng


def _params(cfg, engine, seed=0):
    p = jax.jit(lambda k: llama.init_params(k, cfg.model))(
        jax.random.PRNGKey(seed))
    if engine.quant_weights:
        p = llama.quantize_params(p)
    return engine.shard_params(p)


def _reqs(temp=0.0, **extra):
    """Every prompt spans several prefill chunks (chunk=8), so every
    admission is lane-worthy on a mixed engine — the identity legs
    exercise the fused path, not a serial fallback. Lengths retire at
    different rounds so the lane crosses admissions, finishes, and
    partial occupancy."""
    k = dict(temperature=temp, top_k=0 if temp == 0 else 40, top_p=0.95,
             **extra)
    long_a = [(5 * i + 2) % 199 + 1 for i in range(20)]
    long_b = [(3 * i + 7) % 199 + 1 for i in range(17)]
    return [Request("a", long_a, max_new_tokens=14, **k),
            Request("b", long_b, max_new_tokens=10, **k),
            Request("c", [11, 12] * 5, max_new_tokens=4, **k)]


def _run(tiny_model_kwargs, mixed, program="block", temp=0.0, seed=7,
         reqs=None, obs=None, **kw):
    if program == "verify":
        kw.setdefault("spec_len", 3)
    cfg, eng = _engine(tiny_model_kwargs, mixed, **kw)
    b = ContinuousBatcher(eng, _params(cfg, eng), seed=seed, obs=obs)
    res = b.run(reqs if reqs is not None else _reqs(temp))
    return {u: (r.tokens, r.finish_reason) for u, r in res.items()}, b


def _lane_tokens(b):
    snap = b.obs.registry.snapshot().get(
        "picotron_prefill_lane_tokens_total")
    return sum(snap["values"].values()) if snap else 0


# --------------------------------------------------------------------------- #
# the tentpole: mixed-on == mixed-off across the engine matrix
# --------------------------------------------------------------------------- #


# The full matrix is the gate; ONE canonical leg stays un-marked as the
# tier-1 core (the single-core tier-1 budget is tight — ~25s per leg)
# and the rest ride the `slow` lane (same budget discipline as the
# overlap and speculative matrices; `make test-all` runs the full set).
_slow = pytest.mark.slow
@pytest.mark.parametrize(
    "program,layout,attend,quant,tp,dp,temp,overlap", [
        ("block",  "contiguous", "dense", None,     1, 1, 0.0, False),
        pytest.param("block", "contiguous", "dense", None, 1, 1, 0.9,
                     False, marks=_slow),
        pytest.param("block", "paged", "dense", None, 1, 1, 0.9, False,
                     marks=_slow),
        pytest.param("block", "paged", "flash", None, 1, 1, 0.0, False,
                     marks=_slow),
        pytest.param("block", "contiguous", "dense", "int8kv", 1, 1, 0.9,
                     False, marks=_slow),
        pytest.param("block", "paged", "dense", "int8w", 1, 1, 0.0, False,
                     marks=_slow),
        pytest.param("block", "contiguous", "dense", None, 2, 1, 0.9,
                     False, marks=_slow),
        pytest.param("block", "paged", "dense", None, 1, 2, 0.9, False,
                     marks=_slow),
        pytest.param("verify", "contiguous", "dense", None, 1, 1, 0.9,
                     False, marks=_slow),
        pytest.param("verify", "paged", "dense", None, 1, 2, 0.0, False,
                     marks=_slow),
        pytest.param("block", "contiguous", "dense", None, 1, 1, 0.0,
                     True, marks=_slow),
        pytest.param("block", "paged", "dense", None, 1, 1, 0.9, True,
                     marks=_slow),
        pytest.param("verify", "contiguous", "dense", None, 1, 1, 0.9,
                     True, marks=_slow),
    ])
def test_mixed_identity_matrix(tiny_model_kwargs, program, layout, attend,
                               quant, tp, dp, temp, overlap):
    """Mixed-on emits streams BIT-IDENTICAL to mixed-off — same seed,
    same slot key schedule — for every program family crossed with
    representative kernel/layout/quantization corners, greedy and seeded
    stochastic, on tp=2 and dp=2, with the overlap pipeline composed on
    top. The lane must actually have run (lane token counter moved):
    a leg that silently fell back to serial prefill proves nothing."""
    kw = dict(kv_layout=layout, attend_impl=attend, tp=tp, dp=dp)
    if quant == "int8kv":
        kw["cache_dtype"] = "int8"
    elif quant == "int8w":
        kw["weight_dtype"] = "int8"
    off, b_off = _run(tiny_model_kwargs, False, program, temp, **kw)
    on, b_on = _run(tiny_model_kwargs, True, program, temp,
                    overlap=overlap, **kw)
    assert on == off, (program, layout, attend, quant, tp, dp, temp,
                       overlap)
    assert _lane_tokens(b_on) > 0
    assert _lane_tokens(b_off) == 0
    st = b_on.stats()
    assert st["mixed"] == {"enabled": True, "lanes_active": 0}
    assert all(s is None for s in b_on._slots)  # drained, nothing stuck


@pytest.mark.slow
def test_mixed_lane_chunk_accounting_matches_serial(tiny_model_kwargs):
    """Lane chunks are the SAME chunk schedule the serial path runs:
    ``prefill_dispatches`` (3 + 3 + 2 chunks for the 20/17/10-token
    prompts at chunk=8) agrees across modes, and the lane token counter
    equals the total prompt tokens fed."""
    _, b_off = _run(tiny_model_kwargs, False)
    _, b_on = _run(tiny_model_kwargs, True)
    assert b_on.prefill_dispatches == b_off.prefill_dispatches == 8
    assert _lane_tokens(b_on) == 20 + 17 + 10


@pytest.mark.slow
def test_mixed_removes_solo_prefill_stalls(tiny_model_kwargs):
    """The interference story in one metric: serial admissions that run
    while a decoder is already seated record
    ``picotron_decode_stall_seconds`` (the decode batch sits idle for
    that dispatch); with every prompt lane-worthy, mixed mode records
    NONE — no dispatch ran that did not also advance the decoders."""

    def stall_count(b):
        snap = b.obs.registry.snapshot().get(
            "picotron_decode_stall_seconds")
        if not snap:
            return 0
        return sum(v["count"] for v in snap["values"].values())

    _, b_off = _run(tiny_model_kwargs, False)
    _, b_on = _run(tiny_model_kwargs, True)
    assert stall_count(b_off) >= 1  # 2nd/3rd admission stalls a decoder
    assert stall_count(b_on) == 0


def test_mixed_rejects_round_key_schedule(tiny_model_kwargs):
    """mixed_dispatch + key_schedule='round' is an invalid combination
    (the lane's first token must be keyed by position, not round
    membership): config.validate and the engine both refuse it."""
    cfg = make_config(tiny_model_kwargs, seq=MAX_LEN)
    cfg.inference.mixed_dispatch = True
    cfg.inference.key_schedule = "round"
    with pytest.raises(ValueError, match="key schedule"):
        cfg.validate()
    cfg2 = make_config(tiny_model_kwargs, seq=MAX_LEN)
    with pytest.raises(ValueError, match="key schedule"):
        InferenceEngine(cfg2, slots=2, max_seq_len=MAX_LEN,
                        mixed_dispatch=True, key_schedule="round")


def test_mixed_off_default_leaves_serial_path(tiny_model_kwargs):
    """mixed_dispatch defaults to False: no fused programs are built, no
    lane state exists, and handing ``lanes=`` to the engine is a
    programming error — the serial scheduler is byte-identical to
    before the lane existed."""
    cfg, eng = _engine(tiny_model_kwargs, False)
    assert eng.mixed is False
    assert "lane_out" not in eng._round_fields("decode_block")
    b = ContinuousBatcher(eng, _params(cfg, eng), seed=7)
    assert b._mixed is False and all(ln is None for ln in b._lanes)
    cache = eng.init_cache()
    n = eng.slots
    with pytest.raises(ValueError, match="mixed"):
        eng.decode_block(_params(cfg, eng), cache,
                         np.zeros(n, np.int32),
                         np.zeros((n, 2), np.uint32),
                         np.full(n, -1, np.int32),
                         np.zeros(n, np.int32),
                         np.ones(n, np.float32),
                         np.zeros(n, np.int32),
                         np.ones(n, np.float32),
                         lanes=[None])


@pytest.mark.slow
def test_mixed_cold_short_prompt_admits_serially(tiny_model_kwargs):
    """A cold prompt at or under one chunk keeps the one-shot bucketed
    prefill (a different program family than the chunk the lane runs) —
    so short-prompt streams stay bit-identical to mixed-off by running
    the IDENTICAL serial dispatch, and the lane counter only moves for
    the long prompt."""
    reqs = [Request("s", [3, 1, 4], max_new_tokens=6),
            Request("l", [(5 * i + 2) % 199 + 1 for i in range(20)],
                    max_new_tokens=6)]
    off, _ = _run(tiny_model_kwargs, False,
                  reqs=[Request(**vars(r)) for r in reqs])
    on, b = _run(tiny_model_kwargs, True,
                 reqs=[Request(**vars(r)) for r in reqs])
    assert on == off
    assert _lane_tokens(b) == 20


@pytest.mark.slow
def test_mixed_lane_spans_pass_trace_audit(tiny_model_kwargs, tmp_path):
    """A real mixed run's trace passes the lane-chain audit: every lane
    chunk span parents to its request root and the chunks tile each
    prompt exactly (``--require-lane-chain``, the obs gate for the
    fused path)."""
    from picotron_tpu.obs import Obs, SpanTracer
    from picotron_tpu.tools import trace_dump

    # a PRIVATE span ring: the process-wide GLOBAL_TRACER interleaves
    # every batcher this pytest process has run, so the tiling counts
    # below would otherwise depend on which tests ran first
    _, b = _run(tiny_model_kwargs, True,
                obs=Obs(tracer=SpanTracer()))
    path = tmp_path / "mixed_trace.json"
    b.obs.tracer.dump_chrome(str(path))
    la = trace_dump.lane_chain(trace_dump.load(str(path)))
    assert la["errors"] == []
    assert la["lanes"] == la["linked"] == 8  # 3+3+2 chunks
    assert trace_dump.main([str(path), "--require-lane-chain"]) == 0


# --------------------------------------------------------------------------- #
# composition: isolation re-dispatch under the fused program
# --------------------------------------------------------------------------- #


@pytest.mark.slow
def test_mixed_slot_isolation_redispatch(tiny_model_kwargs):
    """A persistently failing slot under the fused program: the solo
    isolation re-dispatches re-run the lane chunk idempotently (same
    chunk, same rows, same bytes), the faulted slot finishes "error",
    and SURVIVORS' streams equal the fault-free mixed run."""
    clean, _ = _run(tiny_model_kwargs, True, temp=0.9)
    cfg = make_config(tiny_model_kwargs, seq=MAX_LEN)
    cfg.resilience.chaos_dispatch_fail_slot = 1
    cfg.validate()
    on, b = _run(tiny_model_kwargs, True, temp=0.9,
                 hooks=ServingChaos(cfg.resilience))
    assert on["b"][1] == "error"
    for uid in ("a", "c"):
        assert on[uid] == clean[uid]
    assert all(s is None for s in b._slots)
    assert all(ln is None for ln in b._lanes)
    assert b.queue_depth == 0
    assert b.counters["errored"] == 1
    assert b.counters["completed"] == 2


# --------------------------------------------------------------------------- #
# satellite: _prefill_gate defer / preempt branch pins
# --------------------------------------------------------------------------- #


class _FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _gated_batcher(tiny_model_kwargs):
    """A real batcher (serial engine) with a deterministic clock, one
    seated decoder carrying a TPOT SLO, and round budget already spent —
    the configuration in which the gate's defer/preempt branches are
    live."""
    cfg, eng = _engine(tiny_model_kwargs, False)
    clock = _FakeClock()
    b = ContinuousBatcher(eng, _params(cfg, eng), seed=7, clock=clock)
    from picotron_tpu.inference.batcher import _Slot

    holder = Request("held", [1, 2], tpot_slo_ms=50.0)
    b._slots[0] = _Slot(holder, deadline=None, submit_t=None)
    return b, clock


def test_gate_first_admission_of_round_always_passes(tiny_model_kwargs):
    """Branch pin: the progress guarantee. With zero prefill tokens spent
    this round the gate admits ANY prompt — SLO pressure or not — and
    neither counter moves."""
    b, _ = _gated_batcher(tiny_model_kwargs)
    req = Request("r", list(range(1, 30)), tenant="t0")
    assert b._round_prefill_tokens == 0
    assert b._prefill_gate(req) is True
    assert b._tstat(req)["prefill_deferred"] == 0
    assert b._tstat(req)["prefill_preempts"] == 0


def test_gate_without_tpot_slo_never_defers(tiny_model_kwargs):
    """Branch pin: the cap only exists to protect decoders with a TPOT
    SLO. Same spent budget, no SLO on the seated slot -> admit."""
    b, _ = _gated_batcher(tiny_model_kwargs)
    b._slots[0].req.tpot_slo_ms = None
    b._round_prefill_tokens = 8
    assert b._prefill_gate(Request("r", list(range(1, 30)))) is True


def test_gate_defers_and_counts_once_per_decision(tiny_model_kwargs):
    """Branch pin: budget spent + active TPOT SLO + prompt over the
    remaining chunk budget -> defer, ``prefill_deferred`` and the tenant
    counter up by exactly one per decision."""
    b, _ = _gated_batcher(tiny_model_kwargs)
    b._round_prefill_tokens = 8  # one chunk already admitted this round
    req = Request("r", list(range(1, 30)), tenant="t0")
    assert b._prefill_gate(req) is False
    assert b._prefill_gate(req) is False
    assert b._tstat(req)["prefill_deferred"] == 2
    snap = b.obs.registry.snapshot()
    [(lbl, v)] = list(
        snap["picotron_tenant_prefill_deferred_total"]["values"].items())
    assert lbl == 'tenant="t0"' and v == 2
    assert "picotron_tenant_prefill_preempts_total" not in snap


def test_gate_small_request_fits_remaining_budget(tiny_model_kwargs):
    """Branch pin: the cap is a token budget, not a one-admission latch —
    a prompt that still fits under prefill_chunk admits; the ``tokens``
    override prices a lane CHUNK the same way (the lane feed rate)."""
    b, _ = _gated_batcher(tiny_model_kwargs)
    b._round_prefill_tokens = 3
    assert b._prefill_gate(Request("r", [1, 2, 3, 4])) is True  # 3+4 <= 8
    assert b._prefill_gate(Request("r", list(range(1, 30)))) is False
    assert b._prefill_gate(Request("r", list(range(1, 30))),
                           tokens=5) is True


def test_gate_ttft_preempt_overrides_cap(tiny_model_kwargs):
    """Branch pin: a waiting request whose TTFT budget is half spent
    preempts the cap — admit despite the spent budget, with
    ``prefill_preempts`` (not deferred) counting the decision. The
    ``submit_t`` override stands in for the pending-queue clock (the
    lane's slot record carries the time after admission)."""
    b, clock = _gated_batcher(tiny_model_kwargs)
    b._round_prefill_tokens = 8
    req = Request("r", list(range(1, 30)), tenant="t1", ttft_slo_ms=200.0)
    b._submit_t[req.uid] = clock.t
    assert b._prefill_gate(req) is False  # 0ms elapsed: no preempt yet
    clock.t += 0.25  # 250ms >= 200/2
    assert b._prefill_gate(req) is True
    assert b._tstat(req)["prefill_preempts"] == 1
    assert b._tstat(req)["prefill_deferred"] == 1
    del b._submit_t[req.uid]
    assert b._prefill_gate(req, submit_t=clock.t - 0.25) is True
    assert b._prefill_gate(req) is False  # no clock source: cap holds
    assert b._tstat(req)["prefill_preempts"] == 2
