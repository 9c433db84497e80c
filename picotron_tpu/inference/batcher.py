"""Continuous batching: admit/retire variable-length requests into fixed
engine slots.

The engine's decode program has a fixed batch width (``engine.slots``), so
throughput under mixed-length traffic is a scheduling problem: a slot whose
sequence hits EOS must be recycled to a waiting request immediately, not
when the whole batch drains (static batching's tail loss). The batcher is
the host-side loop that does exactly that:

  expire: slots past their wall-clock deadline retire FIRST, so a slot
          freed by a timeout is refilled in the same round, not the next;
  admit:  while a slot is free and requests wait, prefill the next prompt
          (pow-2-bucketed one-shot at or under ``engine.prefill_chunk``,
          chunked straight into the slot above it), and sample its first
          token from the prefill logits;
  decode: ONE ``decode_block`` advances every occupied slot by up to
          ``engine.decode_block_len`` tokens — per-slot sampling params,
          EOS ids, and token budgets ride along as arrays, and the
          EOS/budget stop state lives ON DEVICE, so the host syncs once
          per block instead of once per token (``decode_block_len == 1``
          is the classic per-token loop). On a SPECULATIVE engine
          (``engine.spec_len > 0``) the decode phase is draft-verify
          instead: the drafter proposes ``spec_len`` continuation tokens
          per occupied slot from the slot's own history (host-side,
          between dispatches — free), and one ``engine.verify`` dispatch
          scores, accepts, and rewinds, emitting a VARIABLE 1..spec_len+1
          tokens per slot per dispatch;
  retire: slots that hit EOS or their token budget — decided on device,
          confirmed host-side from the block's produced counts — release
          (a 1-element length write; stale K/V rows become unreachable)
          and free capacity for the next admit. Post-EOS pad tokens in a
          block row are trimmed via the produced counts.

Free slots still flow through the decode program (fixed shapes are the
deal with XLA); they carry a zero budget at length 0 and their outputs are
ignored. The whole loop is deterministic given the seed: one PRNG key
chain, split once per admit and once per in-block step (so the chain —
and with it every sampled stream — is identical across block lengths as
long as requests finish at block boundaries, and identical to the
per-token loop at ``decode_block_len == 1``).

``decode_dispatches`` / ``prefill_dispatches`` / ``generated_tokens``
count engine calls and output tokens across the batcher's lifetime —
``decode_dispatches / generated_tokens`` is dispatches per token, the
count of host syncs a token costs (1 for the per-token loop, ~1/block_len
when every slot stays busy). Speculative runs add ``draft_proposed`` /
``draft_accepted`` (``accept_rate`` = their ratio): an accept rate of r
means the average verify dispatch emitted ~1 + r*spec_len tokens.

**Fault handling** (docs/SERVING.md): every jitted dispatch runs under
``resilience.retry`` with bounded backoff (``resilience.dispatch_attempts``
/ ``dispatch_backoff``). A prefill that still fails costs only the request
being admitted (finish_reason ``"error"``); a decode/verify dispatch that
still fails triggers SLOT ISOLATION — the same round is re-dispatched once
per occupied slot with everyone else's budget masked to 0, so only the
slots that fail alone finish ``"error"`` while the survivors' tokens are
bit-identical to a fault-free round (same shapes, same keys: row b's draw
depends only on row b's logits and the shared key). A failure that
consumed the donated cache (buffers deleted mid-execution) cannot be
isolated: every occupied slot fails ``"error"`` and the cache is rebuilt,
so the PROCESS keeps serving either way — an exception in one dispatch is
never a server death. ``finish()`` accounting is tracked in ``counters``
(admitted/completed/expired/errored/shed) with queue-wait and
time-to-first-token samples surfaced by ``stats()`` — the ``/statz``
payload of tools/serve.py.

**Telemetry** (picotron_tpu/obs, docs/OBSERVABILITY.md): the batcher
records into the ENGINE's metrics registry — ``counters`` is a
``CounterDict`` view over ``picotron_requests_total{state}``, the
queue-wait/TTFT percentile windows live in registry histograms (the same
instruments ``GET /metrics`` renders, so ``/statz`` and Prometheus can
never disagree), and every dispatch's wall/host-sync time lands in
``picotron_dispatch_seconds{kind}``. Spans make one request traceable
end-to-end: a ``request`` root opens at submit; ``queue_wait``,
``prefill`` (radix-hit/dispatch counts), one ``decode``/``verify`` child
per dispatch round (draft len, accepted, host-sync time), and the serve
front end's ``delivery`` all parent to it — ``GET /tracez`` or
``tools/trace_dump.py`` shows the chain. ``obs.enabled: false`` swaps
all of it for no-ops.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from picotron_tpu.inference import sampling
from picotron_tpu.obs import RoundPhases, admit_key
from picotron_tpu.resilience.retry import retry
from picotron_tpu.utils import log0


def _judged_as(kind: str, lane) -> str:
    """The stall judge's key for a round's ``step/issue`` and ``step/sync``:
    the kind of round, and whether a fused prefill lane rode it."""
    return kind + "+lane" if lane else kind


def _sid(span) -> Optional[int]:
    """A span's exportable id (None for no span / the null span's 0)."""
    return span.span_id or None if span is not None else None


def _log_dispatch_failure(kind: str, ident, e: BaseException) -> None:
    log0(f"serving: {kind} dispatch failed for {ident} "
         f"({type(e).__name__}: {e})", flush=True)


@dataclass
class Request:
    """One generation request. ``temperature == 0`` = greedy; ``top_k <= 0``
    and ``top_p >= 1`` disable those filters. ``timeout_s`` is a wall-clock
    budget from admission: a stuck or over-budget request finishes with
    reason "timeout" and frees its slot instead of occupying it forever
    (None = no deadline)."""

    uid: str
    prompt: list
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    timeout_s: Optional[float] = None
    # disaggregated handoff (inference/page_transport.py, paged engines
    # only): a transport payload whose pages are imported at admission.
    # With a ``first_token`` covering the FULL prompt, the slot seats
    # ready to decode — zero prefill dispatches; otherwise the payload is
    # a prefix HINT (imported into the radix cache, the normal admission
    # radix-hits it and prefills only the uncovered suffix).
    kv_import: Optional[dict] = None
    # ---- multi-tenant serving (inference/tenancy.py) ----------------------
    # tenant name ("" = anonymous base traffic: the null adapter, the
    # default radix domain, class-1 priority, no SLOs). The serve front
    # end resolves names against its TenantRegistry and fills the fields
    # below; direct batcher users (bench, tests) set them explicitly.
    tenant: str = ""
    # admission class: higher classes admit first out of the queue; the
    # LOWEST queued class sheds first when the front end's budget gate
    # needs room for a higher-class arrival (shed_lower_priority)
    priority: int = 1
    # resolved adapter pack slot (0 = the reserved null adapter)
    adapter_slot: int = 0
    # SLO targets in milliseconds (None = best-effort): ttft steers
    # admission order and chunked-prefill interleaving; tpot feeds the
    # spec controller's dispatch-width cap and, with ttft, the
    # per-tenant attainment metrics
    ttft_slo_ms: Optional[float] = None
    tpot_slo_ms: Optional[float] = None


@dataclass
class GenerationResult:
    uid: str
    prompt: list
    tokens: list  # generated ids, EOS included when hit
    # "eos" | "length" | "timeout" | "shed" (dropped unstarted at drain) |
    # "error" (dispatch failure isolated to this request)
    finish_reason: str
    queue_wait_s: Optional[float] = None  # submit -> admit (None: never admitted)
    ttft_s: Optional[float] = None  # submit -> first token
    # the request's root span in the process trace ring (None with obs
    # off): late children — the serve front end's delivery span — parent
    # onto it after the batcher has already retired the slot
    span_id: Optional[int] = None
    # decode/verify rounds this request's slot took part in — this
    # request's own dispatches-per-token is dispatches / len(tokens),
    # the per-slot convergence metric the spec controller is judged on
    dispatches: int = 0
    # speculative engines: the slot's spec_len and drafter kind at
    # retirement (the controller's converged choice; the static config
    # values without a controller)
    spec_len_final: Optional[int] = None
    drafter: Optional[str] = None


@dataclass
class _Slot:
    req: Request
    generated: list = field(default_factory=list)
    deadline: Optional[float] = None  # clock() time after which we retire
    submit_t: Optional[float] = None  # clock() at submit (stats)
    queue_wait_s: Optional[float] = None
    ttft_s: Optional[float] = None
    dispatches: int = 0  # rounds this slot was active in
    # mixed_dispatch: the slot is being prefilled chunk-by-chunk through
    # its shard's fused lane — it rides every decode/verify dispatch
    # INACTIVE (budget 0) until the final chunk lands its first token
    prefilling: bool = False


class ContinuousBatcher:
    """Drive an InferenceEngine over a stream of requests.

    >>> b = ContinuousBatcher(engine, params)
    >>> b.submit(Request("a", [1, 2, 3], max_new_tokens=16))
    >>> results = b.run()           # {"a": GenerationResult(...)}

    ``params`` must already be placed on the engine mesh
    (``engine.shard_params``). One batcher owns one cache; interleaving two
    batchers on one engine is fine (separate caches), sharing a cache is
    not (the decode programs consume it).
    """

    def __init__(self, engine, params, seed: int = 0, clock=time.monotonic,
                 drafter=None, on_tokens: Optional[Callable] = None,
                 obs=None):
        self.engine = engine
        self.params = params
        self._clock = clock  # injectable so deadline tests are deterministic
        # telemetry rides on the engine's bundle unless injected: one
        # registry (and the process span ring) covers engine + batcher +
        # front end, so /metrics is a single coherent page
        self.obs = obs if obs is not None else engine.obs
        # the key chain lives on the engine's mesh from the start, so the
        # round's key program (engine.round_keys) and the eager _split()
        # each see ONE input sharding, the first call and every later one
        self._key = jax.device_put(jax.random.PRNGKey(seed),
                                   engine.key_sharding)
        # streaming hook: called as on_tokens(uid, tokens) once a slot and
        # round with the tokens the request emitted in it (a list of one at
        # admission), from inside step()/run() — the serve front end pushes
        # each list into the response stream as one event
        self.on_tokens = on_tokens
        # speculative engines get a drafter (selected by
        # inference.drafter — the prompt-lookup n-gram default or the
        # EAGLE-style learned head — or injected, e.g. a scripted drafter
        # in tests); spec-off engines ignore it
        inf = engine.cfg.inference
        if drafter is None and engine.spec_len > 0:
            from picotron_tpu.inference.speculative import (
                LearnedDrafter,
                NgramDrafter,
            )

            if engine.drafter_kind == "learned":
                drafter = LearnedDrafter(engine, params)
            else:
                drafter = NgramDrafter(engine.spec_ngram,
                                       window=inf.spec_history_window)
        self.drafter = drafter
        # the drafter pool the controller switches between, primary
        # first: a learned primary always carries the free n-gram
        # fallback; an injected custom drafter runs alone
        self._drafters: dict = {}
        if engine.spec_len > 0 and drafter is not None:
            self._drafters[drafter.kind] = drafter
            if drafter.kind == "learned":
                from picotron_tpu.inference.speculative import NgramDrafter

                self._drafters["ngram"] = NgramDrafter(
                    engine.spec_ngram, window=inf.spec_history_window)
        # the closed-loop spec_len policy (inference.spec_controller):
        # per-slot draft lengths + drafter choice, fed by the registry's
        # live accept counters and dispatch-latency histograms
        self.controller = None
        if engine.spec_len > 0 and inf.spec_controller.enabled:
            from picotron_tpu.inference.speculative import SpecController

            self.controller = SpecController(
                inf.spec_controller, self.obs.registry,
                slots=engine.slots, max_spec_len=engine.spec_len,
                block_len=engine.decode_block_len,
                kinds=tuple(self._drafters))
        # the learned drafter's input: each slot's last hidden state,
        # kept ON DEVICE between dispatches (engine.return_hidden)
        self._hidden = None
        if engine.return_hidden:
            self._hidden = jnp.zeros(
                (engine.slots, engine.cfg.model.hidden_size),
                jnp.dtype(engine.cfg.model.dtype))
        # engine, weights and cache are in one hand here first: the store's
        # narrow chunk program is built on the fresh cache (once a process)
        self._cache = engine.build_narrow(params, engine.init_cache())
        self._slots: list = [None] * engine.slots
        self._pending: deque = deque()
        self._results: dict = {}
        n = engine.slots
        self._last_tok = np.zeros(n, np.int32)
        # an engine that generates by blocks (``engine.blocks``) is fed no
        # last token: a slot's next block starts from ``_given[i,
        # :_given_n[i]]``, the prompt's remainder behind its whole prefilled
        # blocks in the slot's first round and nothing after it
        self._blocks = bool(getattr(engine, "blocks", False))
        self._given = np.zeros(
            (n, engine.cfg.model.block_length if self._blocks else 1),
            np.int32)
        self._given_n = np.zeros(n, np.int32)
        # and is handed back its WAITING block: the last block of its last
        # round, which that round streamed and did not store (the slot's
        # next round stores it inside its first forward,
        # ``engine._fused_forward``); -1 throughout: nothing waits
        self._waiting = np.full_like(self._given, -1)
        self._temp = np.zeros(n, np.float32)
        self._top_k = np.zeros(n, np.int32)
        self._top_p = np.ones(n, np.float32)
        self._eos = np.full(n, -1, np.int32)
        self._budget = np.zeros(n, np.int32)
        # per-slot adapter pack slots (multi-tenant engines): every
        # decode/verify dispatch ships this [slots] row so one dispatch
        # mixes tenants; 0 (the null adapter) for free/base slots
        self._adapter = np.zeros(n, np.int32)
        # lifetime dispatch/throughput counters (bench + tests)
        self.decode_dispatches = 0
        self.prefill_dispatches = 0
        self.generated_tokens = 0
        self.draft_proposed = 0
        self.draft_accepted = 0
        # dp rebalance accounting (the planner in _rebalance): completed
        # cross-shard slot migrations and the raw page bytes they moved
        self.rebalance_count = 0
        self.rebalance_bytes = 0
        self._rebalance_cooloff = 0  # rounds to sit out after a migration
        # request accounting: every submitted request lands in exactly one
        # terminal counter (completed = eos|length, expired = timeout,
        # errored = dispatch failure, shed = dropped unstarted) — the
        # serve-chaos acceptance sums these against submissions. A
        # CounterDict: plain-dict reads/compares, writes mirrored into
        # the registry as picotron_requests_total{state}.
        reg = self.obs.registry
        self.counters = reg.counter_dict(
            "picotron_requests_total",
            ("admitted", "completed", "expired", "errored", "shed"),
            help="request accounting by terminal state (+ admitted)")
        self._submit_t: dict = {}  # uid -> clock() at submit
        # latency windows (the /statz percentile payloads AND the
        # /metrics histograms — one instrument, two renderings)
        self._queue_wait_hist = reg.histogram(
            "picotron_queue_wait_seconds", "submit -> admit")
        self._ttft_hist = reg.histogram(
            "picotron_ttft_seconds", "submit -> first token")
        self._tokens_total = reg.counter(
            "picotron_generated_tokens_total", "tokens emitted to streams")
        self._stream_events_total = reg.counter(
            "picotron_stream_events_total",
            "hand-offs of tokens to streams, one a slot and round "
            "(generated tokens / this = tokens an event)")
        self._draft_proposed_total = reg.counter(
            "picotron_draft_proposed_total",
            "draft tokens proposed (speculative engines)")
        self._draft_accepted_total = reg.counter(
            "picotron_draft_accepted_total",
            "draft tokens accepted into emitted streams")
        # disaggregation: payload imports that carried a usable remote
        # prefix, and admissions seated directly from a handoff (zero
        # prefill dispatches) — the cross-replica acceptance counters
        self._remote_hits_total = reg.counter(
            "picotron_prefix_remote_hits_total",
            "transport imports that landed a remote-prefilled prefix")
        # pre-register both migration outcomes so /metrics carries the
        # family (at 0) from the first scrape, not from the first move
        for outcome in ("ok", "aborted"):
            reg.counter("picotron_slot_migrations_total",
                        "cross-shard slot migrations by outcome",
                        outcome=outcome)
        self.handoff_seated = 0
        # per-tenant accounting (multi-tenant serving): a host-side tally
        # for /statz next to the labeled picotron_tenant_* registry
        # families — one instrument set, two renderings, like the global
        # counters above
        self._tenant_stats: dict = {}
        self._tenant_tokens_total: dict = {}  # tenant -> its token counter
        # prefill tokens admitted THIS scheduler round (the SLO-aware
        # chunked-prefill interleaving budget — see _prefill_gate)
        self._round_prefill_tokens = 0
        self._req_spans: dict = {}  # uid -> live request root span
        self._last_prefill: dict = {}  # scratch: dispatch/radix-hit counts
        self._host_sync_s = 0.0  # scratch: last dispatch's host-sync time
        self._retry = dict(
            attempts=engine.cfg.resilience.dispatch_attempts,
            backoff=engine.cfg.resilience.dispatch_backoff,
            desc="serving dispatch")
        # ---- overlapped (zero-bubble) scheduling state --------------------
        # inference.overlap: issue dispatch N+1 BEFORE syncing dispatch N
        # (_step_overlap). The engine resolved the knobs at construction;
        # the batcher mirrors them so every branch below is one attribute
        # read, and flips the engine to deferred page-table advance: under
        # overlap the paged host_len bookkeeping lands at SYNC time (after
        # the late-stop mask) via engine.apply_advance, never inside the
        # dispatch wrapper.
        self._overlap = bool(getattr(engine, "overlap", False))
        self._sched = getattr(engine, "key_schedule", "round")
        if self._overlap:
            engine.defer_advance = True
        # per-slot PRNG bases (key_schedule == "slot"): the token at
        # 0-based sequence index p is keyed fold_in(base, p - 1) no matter
        # how positions are grouped into rounds — the round-count-
        # independent schedule the overlap bit-identity gate rests on
        # (docs/INFERENCE.md "Overlapped scheduling"). One _split() per
        # admit seeds the base: the same chain link the round schedule
        # spends on its admit key, so admission order fixes the streams.
        self._base_keys = np.zeros((n, 2), np.uint32)
        # occupancy epoch per slot: bumped at finish/admit/migrate. The
        # in-flight round snapshots it at issue; sync drops any row whose
        # epoch moved (late stop, re-seat) — the exactly-once guarantee.
        self._epoch = np.zeros(n, np.int64)
        self._inflight = None   # issued-not-yet-synced round record
        self._dev_last = None   # device-resident [slots] last-token row
        self._round_seq = 0     # issues so far (span labels, /statz stalls)
        # scheduling-gap instrumentation (BOTH modes): host time between
        # one round's sync end and the next issue — what overlap exists
        # to hide. 0.0 whenever a round is still in flight at issue.
        self._t_last_sync_end = None
        self._ov_device_s = 0.0       # summed issue -> sync-end windows
        self._ov_t0 = None            # first issue (efficiency wall start)
        self._ov_t1 = None            # last sync end (efficiency wall end)
        self._gap_hist = reg.histogram(
            "picotron_dispatch_gap_seconds",
            "issue-to-issue scheduling gap net of device time")
        # the round's wall time tiled into step/plan, step/admit,
        # step/issue, step/sync, step/deliver (docs/OBSERVABILITY.md), each
        # judged against its own recent past ("Stalls", same document)
        self._phases = RoundPhases(self.obs)
        self.obs.stalls.register("step/plan", "step/admit", "step/issue",
                                 "step/sync", "step/deliver")
        # what the block's layers and the engine's rounds count
        # (``engine.stat_names``; nothing with the Llama block), added where
        # a round is delivered
        self._model_counters = [
            reg.counter(f"picotron_{name}_total",
                        "counted inside the programs by the model's layers",
                        **labels)
            for name, labels in engine.stat_names]
        self._prefill_tokens_total = reg.counter(
            "picotron_prefill_tokens_total",
            "prompt tokens run through a prefill program (solo, chunked "
            "or lane), cached prefix excluded")
        # ---- mixed prefill–decode dispatch (inference.mixed_dispatch) -----
        # one prefill LANE per dp shard rides every decode/verify
        # dispatch (engine._lane_chunk): a long-prompt admission is
        # seated immediately (prefilling=True, budget 0) and its prompt
        # is fed through the lane one fixed-width chunk per round — no
        # solo prefill dispatch ever stalls the decoders behind it. Each
        # lane record tracks one such admission: its slot/epoch/request,
        # the full prompt ids, the radix-cached prefix it resumed past,
        # done_end (rows CONFIRMED landed), fed_end (rows fed — one
        # chunk ahead of done_end while a round is in flight under
        # overlap), the admit-time fold key the final chunk's
        # first-token draw consumes, and the open prefill span.
        self._mixed = bool(getattr(engine, "mixed", False))
        self._lanes: list = [None] * engine.dp_size
        self._lane_scratch = None  # last dispatch's (lane_out, lane_hid)
        # leaf lock for the scratch fields a stats() scrape may read from
        # another thread while the dispatch loop mutates them
        # (_host_sync_s, _last_prefill). Strictly a leaf: no other lock
        # and no blocking call is ever taken inside it (picolint
        # PICO-C002/C003 pin this in tests/test_analysis.py).
        self._scratch_mu = threading.Lock()

    @property
    def accept_rate(self) -> Optional[float]:
        """Fraction of proposed draft tokens that entered an emitted
        stream (None before any speculative dispatch)."""
        if not self.draft_proposed:
            return None
        return self.draft_accepted / self.draft_proposed

    # ---- queue surface ----------------------------------------------------

    def submit(self, req: Request, waited=None) -> None:
        """Queue ``req``. ``waited`` is the caller's span of what came
        before this call on the request's behalf (the front end's wait
        for its lock): the request root begins where that span began and
        adopts it as its first child, so the chain starts where the
        client's wait does."""
        if not req.prompt:
            # fail at submission, not inside run(): an admit-time prefill
            # error would throw away every already-finished result
            raise ValueError(f"request {req.uid!r}: empty prompt")
        if req.max_new_tokens < 1:
            # a zero-budget request would occupy a slot forever: _remaining()
            # is 0 from admission on, so _tokens_done() never fires to retire it
            raise ValueError(
                f"request {req.uid!r}: max_new_tokens must be >= 1 "
                f"(got {req.max_new_tokens})")
        if (req.uid in self._submit_t or req.uid in self._results
                or any(s is not None and s.req.uid == req.uid
                       for s in self._slots)):
            # a duplicate would silently overwrite the first request's
            # result (and its queue-wait clock) — fail at submission like
            # the other contract violations above
            raise ValueError(
                f"request {req.uid!r}: duplicate uid (queued, in flight, "
                f"or finished with an untaken result)")
        budget = self.engine.max_seq_len - len(req.prompt)
        if budget < 1:
            raise ValueError(
                f"request {req.uid!r}: prompt of {len(req.prompt)} tokens "
                f"leaves no room to generate under max_seq_len "
                f"{self.engine.max_seq_len}")
        self._submit_t[req.uid] = self._clock()
        # the request's root span: every later stage (queue wait, prefill,
        # per-dispatch decode/verify, the front end's delivery) parents to
        # it, so one request reads as one tree in a trace dump
        root = self._req_spans[req.uid] = self.obs.tracer.begin(
            "request", uid=req.uid, prompt_tokens=len(req.prompt),
            max_new_tokens=req.max_new_tokens)
        if waited is not None:
            self.obs.tracer.adopt(root, waited)
        self._pending.append(req)

    @property
    def busy(self) -> bool:
        return (bool(self._pending)
                or any(s is not None for s in self._slots)
                or self._inflight is not None)

    @property
    def queue_depth(self) -> int:
        """Requests waiting for a slot (the bounded-queue admission gate)."""
        return len(self._pending)

    def commitment(self, req) -> int:
        """Worst-case tokens ``req`` can actually occupy: prompt plus its
        generation budget capped by the sequence window (``_remaining()``
        enforces the same cap at decode time), so a huge ``max_new_tokens``
        counts what it can consume, not what it asked for. The admission
        gate (serve.py) prices requests with this BEFORE submit-time
        validation, hence the clamp for over-window prompts."""
        return len(req.prompt) + max(0, min(
            req.max_new_tokens, self.engine.max_seq_len - len(req.prompt)))

    def token_load(self) -> int:
        """Worst-case token commitment of every queued and in-flight
        request — the token-budget admission-control metric: what the
        cache/compute would owe if every live request ran to its cap."""
        load = sum(self.commitment(r) for r in self._pending)
        for s in self._slots:
            if s is not None:
                load += self.commitment(s.req)
        return load

    @property
    def paged(self):
        """The engine's host page allocator (None on the contiguous
        layout) — the admission gate prices in pages against it."""
        return self.engine.paged

    def page_commitment(self, req) -> int:
        """Worst-case POOL PAGES ``req`` can occupy — the paged layout's
        admission price: ``ceil(commitment / page_len)``, not a
        contiguous ``max_seq_len`` strip. Prefix hits only make the
        actual footprint smaller (shared pages are counted once, in the
        holder that wrote them). The price covers the dispatch overshoot
        rows too — a stopped slot's ghost rewrite (+1) or the verify's
        optimistic ``spec_len`` draft rows past the cap — clamped at the
        per-slot window, so a priced admission can never starve
        decode-time allocation."""
        overshoot = (self.engine.spec_len if self.engine.spec_len > 0
                     else 1)
        return min(self.paged.pages_for(self.commitment(req) + overshoot),
                   self.paged.max_pages)

    def page_load(self) -> int:
        """Worst-case page commitment of every queued and in-flight
        request (the serve front end's 429 gate on the paged layout)."""
        load = sum(self.page_commitment(r) for r in self._pending)
        for s in self._slots:
            if s is not None:
                load += self.page_commitment(s.req)
        return load

    # ---- multi-tenant accounting ------------------------------------------

    @staticmethod
    def _tname(req: Request) -> str:
        """The request's tenant label ("" renders as "base" — anonymous
        traffic is itself a tenant in the metric families)."""
        return req.tenant or "base"

    def _tstat(self, req: Request) -> dict:
        name = self._tname(req)
        st = self._tenant_stats.get(name)
        if st is None:
            st = {"admitted": 0, "completed": 0, "expired": 0,
                  "errored": 0, "shed": 0, "tokens": 0,
                  "slo_ttft_met": 0, "slo_ttft_missed": 0,
                  "slo_tpot_met": 0, "slo_tpot_missed": 0,
                  "prefill_deferred": 0, "prefill_preempts": 0}
            self._tenant_stats[name] = st
        return st

    def _tenant_tokens(self, req: Request):
        """The tenant's ``picotron_tenant_tokens_total`` child, resolved in
        the registry once a tenant: delivery bumps it every slot and round."""
        name = self._tname(req)
        c = self._tenant_tokens_total.get(name)
        if c is None:
            c = self._tenant_tokens_total[name] = self.obs.registry.counter(
                "picotron_tenant_tokens_total",
                "tokens emitted to streams, by tenant", tenant=name)
        return c

    def _tenant_count(self, req: Request, state: str) -> None:
        self._tstat(req)[state] += 1
        self.obs.registry.counter(
            "picotron_tenant_requests_total",
            "request accounting by tenant and terminal state (+ admitted)",
            tenant=self._tname(req), state=state).inc()

    def _tenant_slo(self, req: Request, slo: str, met: bool) -> None:
        outcome = "met" if met else "missed"
        self._tstat(req)[f"slo_{slo}_{outcome}"] += 1
        self.obs.registry.counter(
            "picotron_tenant_slo_total",
            "per-tenant SLO attainment by target and outcome",
            tenant=self._tname(req), slo=slo, outcome=outcome).inc()

    def tenant_token_load(self, tenant: str) -> int:
        """Worst-case token commitment of ONE tenant's queued and
        in-flight requests — the per-tenant quota gate's price (the same
        ladder ``token_load`` prices globally)."""
        load = sum(self.commitment(r) for r in self._pending
                   if (r.tenant or "") == tenant)
        for s in self._slots:
            if s is not None and (s.req.tenant or "") == tenant:
                load += self.commitment(s.req)
        return load

    def tenant_page_load(self, tenant: str) -> int:
        """Worst-case page commitment of one tenant's queued and
        in-flight requests (paged layout; 0 on contiguous)."""
        if self.paged is None:
            return 0
        load = sum(self.page_commitment(r) for r in self._pending
                   if (r.tenant or "") == tenant)
        for s in self._slots:
            if s is not None and (s.req.tenant or "") == tenant:
                load += self.page_commitment(s.req)
        return load

    def shed_lower_priority(self, priority: int, tokens: int = 0,
                            pages: int = 0) -> tuple:
        """Shed QUEUED requests of a class strictly below ``priority`` —
        lowest class first, newest first within a class — until the freed
        worst-case commitment covers ``tokens`` AND ``pages`` (0 = no
        demand on that budget) or no lower-class request remains. The
        serve front end's admission gate calls this before 429ing a
        higher-class arrival: the lowest class sheds first while higher
        classes hold their admission. Returns (tokens_freed,
        pages_freed)."""
        freed_t = freed_p = 0
        while freed_t < tokens or freed_p < pages:
            best = None
            for j, r in enumerate(self._pending):
                if r.priority >= priority:
                    continue
                # <= keeps the LATEST of the lowest class: the request
                # that waited least loses first
                if (best is None
                        or r.priority <= self._pending[best].priority):
                    best = j
            if best is None:
                break
            req = self._pending[best]
            freed_t += self.commitment(req)
            if self.paged is not None:
                freed_p += self.page_commitment(req)
            del self._pending[best]
            self._submit_t.pop(req.uid, None)
            self.counters["shed"] += 1
            self._results[req.uid] = self._shed_result(req)
        return freed_t, freed_p

    def take_results(self) -> dict:
        """Drain finished results accumulated since the last call:
        {uid: GenerationResult}. The serve loop calls this after each
        step(); run() uses it for its final return."""
        out, self._results = self._results, {}
        return out

    def shed_pending(self) -> None:
        """Finish every QUEUED (never admitted) request with reason
        ``"shed"`` — the graceful-drain path: in-flight slots run to
        completion, but work that never started is handed back so the
        client can retry against another replica instead of waiting on a
        server that is exiting."""
        while self._pending:
            req = self._pending.popleft()
            self._submit_t.pop(req.uid, None)
            self.counters["shed"] += 1
            self._results[req.uid] = self._shed_result(req)

    def _shed_result(self, req: Request) -> GenerationResult:
        """Terminal "shed" result + its ended root span."""
        self._tenant_count(req, "shed")
        span = self._req_spans.pop(req.uid, None)
        if span is not None:
            self.obs.tracer.end(span, finish_reason="shed")
        return GenerationResult(
            req.uid, list(req.prompt), [], "shed", span_id=_sid(span))

    def run(self, requests=None) -> dict:
        """Submit ``requests`` (optional) and step until every submitted
        request has finished. Returns {uid: GenerationResult}."""
        for r in requests or ():
            self.submit(r)
        while self.busy:
            self.step()
        return self.take_results()

    def refresh_gauges(self) -> tuple:
        """Re-read live occupancy into the registry gauges; returns
        ``(queued, active)``. Called by ``stats()`` AND by the serve
        front end's ``/metrics`` render, so a Prometheus scraper that
        never touches ``/statz`` still sees current depth/occupancy.
        Safe from any thread: a deque ``len`` and one pass over the
        fixed-size slot list, no batcher state mutated."""
        queued = len(self._pending)
        active = sum(s is not None for s in self._slots)
        reg = self.obs.registry
        reg.gauge("picotron_queue_depth",
                  "requests waiting for a slot").set(queued)
        reg.gauge("picotron_active_slots",
                  "slots holding a live request").set(active)
        # dp-sharded batching: the mesh width and each shard's occupancy
        # (host-side slot-list walk — see shard_occupancy) so the router
        # and fleet controller see ONE bigger replica, not N small ones.
        # Present at dp=1 too (shard "0"), so scrapers never branch.
        reg.gauge("picotron_dp_size",
                  "dp shards of this logical engine").set(
                      self.engine.dp_size)
        for sidx, occ in enumerate(self.shard_occupancy()):
            reg.gauge("picotron_shard_occupancy",
                      "occupied slots by dp shard",
                      shard=str(sidx)).set(occ)
        if self.paged is not None:
            # pool occupancy on /metrics, not just /statz: the router's
            # least-loaded scoring reads it straight off the scrape
            total = self.paged.pool.usable_pages
            live = self.paged.pool.live_count
            reg.gauge("picotron_kv_pages_live",
                      "KV pool pages holding live tokens").set(live)
            reg.gauge("picotron_kv_pool_utilization",
                      "live / usable KV pool pages").set(
                          live / max(total, 1))
        if self.engine.spec_len > 0:
            # speculation health on the scrape (refreshed on render like
            # the depth gauges above): the fabric's router — and any
            # Prometheus scraper — sees each replica's live accept rate
            # and effective per-slot draft length
            reg.gauge("picotron_spec_accept_rate",
                      "fraction of proposed draft tokens accepted").set(
                          self.accept_rate or 0.0)
            reg.gauge("picotron_spec_len",
                      "mean effective draft length over occupied slots"
                      ).set(self.spec_len_effective())
        # per-tenant occupancy + page commitment on the scrape — the
        # router's tenant-aware placement reads these off /metrics
        queued_by: dict = {}
        for r in self._pending:
            name = self._tname(r)
            queued_by[name] = queued_by.get(name, 0) + 1
        active_by: dict = {}
        pages_by: dict = {}
        for s in self._slots:
            if s is None:
                continue
            name = self._tname(s.req)
            active_by[name] = active_by.get(name, 0) + 1
            if self.paged is not None:
                pages_by[name] = (pages_by.get(name, 0)
                                  + self.page_commitment(s.req))
        for name in (set(self._tenant_stats) | set(queued_by)
                     | set(active_by)):
            reg.gauge("picotron_tenant_queue_depth",
                      "queued requests by tenant",
                      tenant=name).set(queued_by.get(name, 0))
            reg.gauge("picotron_tenant_active_slots",
                      "occupied slots by tenant",
                      tenant=name).set(active_by.get(name, 0))
            if self.paged is not None:
                reg.gauge("picotron_tenant_pages_committed",
                          "worst-case page commitment of live slots, "
                          "by tenant",
                          tenant=name).set(pages_by.get(name, 0))
        return queued, active

    def spec_len_effective(self) -> float:
        """Mean draft length across occupied slots: the controller's live
        per-slot choices, or the static ``engine.spec_len`` without one
        (0.0 when nothing is parked or speculation is off)."""
        occ = [i for i, s in enumerate(self._slots) if s is not None]
        if self.engine.spec_len <= 0 or not occ:
            return 0.0
        if self.controller is not None:
            return self.controller.spec_len_mean(occ)
        return float(self.engine.spec_len)

    def stats(self) -> dict:
        """Serving counters + latency percentiles (the ``/statz`` payload):
        request accounting (admitted/completed/expired/errored/shed),
        dispatch/throughput counters, live occupancy, and queue-wait /
        time-to-first-token percentiles over the retained samples."""
        queued, active = self.refresh_gauges()
        d = dict(self.counters)
        d.update(
            decode_dispatches=self.decode_dispatches,
            prefill_dispatches=self.prefill_dispatches,
            generated_tokens=self.generated_tokens,
            queued=queued,
            active_slots=active,
            slots=len(self._slots),
            queue_wait_s=self._queue_wait_hist.percentiles(),
            ttft_s=self._ttft_hist.percentiles(),
        )
        # what the cache is (engine.__init__ reads both off its shapes):
        # resident bytes, and for K/V leaves the heads a lane row holds
        d["kv_cache_bytes"] = self.engine.kv_cache_bytes
        # what the prefill programs ran, padding included, beside the
        # prompt tokens they were asked for; chunk dispatches by width
        d["prefill_tokens"] = int(self._prefill_tokens_total.value)
        d["prefill_rows"] = int(self.engine.prefill_rows_total.value)
        d["prefill_chunks"] = {
            str(w): int(c.value)
            for w, c in self.engine.prefill_chunks_total.items()}
        if self.engine.kv_pack is not None:
            d["kv_pack_factor"] = self.engine.kv_pack
        if self.draft_proposed:
            d["accept_rate"] = self.accept_rate
        if self.engine.spec_len > 0:
            d["spec_len_effective"] = self.spec_len_effective()
            if self.controller is not None:
                d["spec_controller"] = self.controller.decisions
        if self.paged is not None:
            # pool occupancy + prefix-cache effectiveness (kv_pages_*,
            # prefix_hit_rate, cow_copies, ...) ride into /statz
            d.update(self.paged.stats())
            # disaggregation: admissions seated straight from an imported
            # handoff (zero prefill dispatches) + remote prefix imports
            d["handoff_seated"] = self.handoff_seated
            d["prefix_remote_hits"] = int(self._remote_hits_total.value)
        if self.engine.store is not None:
            # the contiguous layout's prefix store: occupancy, and the hit
            # rate under the paged layout's keys
            d.update(self.engine.store.stats())
            d["prefix_store_bytes"] = self.engine.store_bytes
        if self._tenant_stats:
            # the /statz rendering of the picotron_tenant_* families
            d["tenants"] = {name: dict(st)
                            for name, st in self._tenant_stats.items()}
        # dp-sharded batching: one logical engine's width and balance.
        # Set AFTER paged.stats() so the batcher's slot-list occupancy
        # (the scheduler's view) wins over the allocator's host_len view.
        d["dp_size"] = self.engine.dp_size
        d["slots_total"] = len(self._slots)
        d["shard_occupancy"] = self.shard_occupancy()
        d["rebalance_count"] = self.rebalance_count
        d["rebalance_bytes"] = self.rebalance_bytes
        # scratch the dispatch/admission loop overwrites mid-round: a
        # stats() scrape from another thread (the serve /statz handler)
        # snapshots them under the same leaf lock every writer holds
        with self._scratch_mu:
            d["last_host_sync_s"] = self._host_sync_s
            d["last_prefill"] = dict(self._last_prefill)
        # what /statz shows of the scheduling gap (obs-smoke reads it):
        # issue-to-issue gap percentiles from the histogram's retained
        # samples, plus the device-busy fraction
        ov = dict(enabled=self._overlap,
                  dispatch_gap_s=self._gap_hist.percentiles())
        if self._ov_t0 is not None and self._ov_t1 is not None:
            wall = max(self._ov_t1 - self._ov_t0, 1e-9)
            ov["device_busy_s"] = self._ov_device_s
            ov["wall_s"] = wall
            ov["overlap_efficiency"] = min(1.0, self._ov_device_s / wall)
        d["overlap"] = ov
        # the stall judge's totals a phase and its slowest rounds' records
        # (docs/OBSERVABILITY.md "Stalls")
        d["stalls"] = self.obs.stalls.stats()
        # mixed prefill–decode dispatch: whether the fused lane family is
        # compiled in, and how many shard lanes are mid-prompt right now
        d["mixed"] = dict(
            enabled=self._mixed,
            lanes_active=sum(ln is not None for ln in self._lanes))
        return d

    # ---- one scheduler round ----------------------------------------------

    def _split(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    def _dev_tok(self):
        """The device-resident [slots] last-token row (overlap only): the
        next round's input tokens come from here, so issuing round N+1
        never waits on a host materialization of round N. The slot
        programs' ``next_tok`` output replaces it wholesale at issue;
        admissions and rebalance patch individual rows in lockstep with
        ``_last_tok``."""
        if self._dev_last is None:
            self._dev_last = jnp.asarray(self._last_tok)
        return self._dev_last

    _REASON_COUNTER = {"eos": "completed", "length": "completed",
                       "timeout": "expired", "error": "errored",
                       "shed": "shed"}

    def _finish(self, i: int, reason: str) -> None:
        s = self._slots[i]
        self.counters[self._REASON_COUNTER[reason]] += 1
        if reason != "shed":  # shed requests count via _shed_result
            self._tenant_count(s.req, self._REASON_COUNTER[reason])
        if (len(s.generated) > 1 and s.ttft_s is not None
                and s.submit_t is not None):
            # finish-time mean time-per-output-token: the decode half of
            # the request's latency, the per-tenant TPOT instrument
            tpot = ((self._clock() - s.submit_t - s.ttft_s)
                    / (len(s.generated) - 1))
            self.obs.registry.histogram(
                "picotron_tenant_tpot_seconds",
                "mean per-token decode latency by tenant",
                tenant=self._tname(s.req)).observe(tpot)
            if s.req.tpot_slo_ms is not None:
                self._tenant_slo(s.req, "tpot",
                                 tpot * 1000.0 <= s.req.tpot_slo_ms)
        span = self._req_spans.pop(s.req.uid, None)
        if span is not None:
            self.obs.tracer.end(span, finish_reason=reason,
                                tokens=len(s.generated))
        spec_len = drafter_kind = None
        if self.engine.spec_len > 0:
            if self.controller is not None:
                spec_len = int(self.controller.lens()[i])
                drafter_kind = self.controller.drafter_kinds()[i]
            else:
                spec_len = self.engine.spec_len
                drafter_kind = (self.drafter.kind if self.drafter is not None
                                else None)
        self._results[s.req.uid] = GenerationResult(
            s.req.uid, list(s.req.prompt), list(s.generated), reason,
            queue_wait_s=s.queue_wait_s, ttft_s=s.ttft_s,
            span_id=_sid(span), dispatches=s.dispatches,
            spec_len_final=spec_len, drafter=drafter_kind)
        for d in self._drafters.values():
            d.forget(s.req.uid)
        self._slots[i] = None
        # retire bumps the seat's epoch: any in-flight round that was
        # issued against this occupant drops the row at sync
        self._epoch[i] += 1
        if self._mixed:
            # a lane occupant retiring mid-prompt (timeout, dispatch
            # error) abandons its lane; a chunk still in flight is
            # isolated by the epoch bump above
            sh = i // self.engine.slots_per_shard
            if (self._lanes[sh] is not None
                    and self._lanes[sh]["slot"] == i):
                self._lane_drop(sh, reason)
        self._cache = self.engine.release(self._cache, i)
        self._last_tok[i] = 0
        self._given_n[i] = 0
        self._waiting[i] = -1  # a stream that ended owes its block nothing
        self._temp[i] = 0.0
        self._top_k[i] = 0
        self._top_p[i] = 1.0
        self._eos[i] = -1
        self._budget[i] = 0
        self._adapter[i] = 0

    def _remainder(self, prompt) -> int:
        """Tokens of ``prompt`` behind its whole blocks, which lead the
        slot's first block (0 for an engine that generates token by
        token)."""
        return len(prompt) % self._given.shape[1] if self._blocks else 0

    def _remaining(self, i: int) -> int:
        """Tokens slot i may still produce: its max_new_tokens budget capped
        by the sequence window — the host truth the device's on-block
        budget state mirrors."""
        s = self._slots[i]
        r = s.req
        cap = min(r.max_new_tokens,
                  self.engine.max_seq_len - len(r.prompt))
        return max(cap - len(s.generated), 0)

    def _tokens_done(self, i: int, toks: list) -> None:
        """Record the tokens slot i produced this round (a list of one at
        admission), in order; retire on EOS/budget. The stop is found once:
        the request's EOS if the round drew it, else the budget / window
        cut; rows behind it are dropped. One accounting pass and one
        ``on_tokens`` call, whatever the count."""
        s = self._slots[i]
        r = s.req
        # submit() holds every request to room for one token at least
        room = max(self._remaining(i), 1)
        toks = toks[:room]
        reason = "length" if len(toks) == room else None
        if r.eos_id is not None and r.eos_id in toks:
            toks = toks[: toks.index(r.eos_id) + 1]
            reason = "eos"
        n = len(toks)
        s.generated.extend(toks)
        self.generated_tokens += n
        self._tokens_total.inc(n)
        self._stream_events_total.inc()
        self._tstat(r)["tokens"] += n
        self._tenant_tokens(r).inc(n)
        if s.ttft_s is None and s.submit_t is not None:
            s.ttft_s = self._clock() - s.submit_t
            self._ttft_hist.observe(s.ttft_s)
            self.obs.registry.histogram(
                "picotron_tenant_ttft_seconds",
                "submit -> first token, by tenant",
                tenant=self._tname(r)).observe(s.ttft_s)
            if r.ttft_slo_ms is not None:
                self._tenant_slo(r, "ttft",
                                 s.ttft_s * 1000.0 <= r.ttft_slo_ms)
        if self.on_tokens is not None:
            self.on_tokens(r.uid, toks)
        if reason is not None:
            self._finish(i, reason)
        else:
            self._last_tok[i] = toks[-1]

    def _deliver_round(self, toks, counts) -> None:
        """Hand every live slot the prefix it produced this round
        (``toks[i, :counts[i]]``). The device already stopped each row at
        EOS/budget; ``_tokens_done`` applies the same rules host-side."""
        rows, counts = toks.tolist(), counts.tolist()
        for i, n in enumerate(counts):
            if n > 0 and self._slots[i] is not None:
                self._tokens_done(i, rows[i][:n])

    def _prefill_into(self, req: Request, i: int, key=None):
        """Prefill ``req`` into slot ``i`` (one-shot or chunked) and return
        its last-token logits — or, on a ``sample_on_device`` engine
        (``key`` is then the admit-time PRNG key), the first sampled token
        [1] int32: the fused epilogue draws it inside the prefill dispatch
        from the slot's own sampling params, so the [1, V] logits never
        cross to the host. Mutates the cache/dispatch counters. On the
        paged layout the engine's prefix-sharing admission runs instead:
        the longest radix-cached prefix is shared (no dispatches) and only
        the suffix prefills; a contiguous engine with a prefix store
        (``engine.store``) copies the retained prefix into the slot and
        prefills the suffix the same way. An engine that generates by
        blocks prefills the prompt's whole blocks alone (none: no dispatch),
        and what comes back is read by nobody: its logits score the token
        AT a position, and the remainder is the first block's."""
        sample = None
        rh = self.engine.return_hidden
        hidden = None
        # a blocks engine: whole blocks only, the remainder is the first
        # block's; a prompt shorter than a block prefills nothing
        prompt = req.prompt[: len(req.prompt) - self._remainder(req.prompt)]
        if not prompt:
            with self._scratch_mu:
                self._last_prefill = {"dispatches": 0}
            return None
        if self.engine.sample_on_device:
            sample = (key, req.temperature, req.top_k, req.top_p)
        # the tenant's adapter rides the prefill dispatch as a single-row
        # id; adapter-less engines pass nothing and trace the base program
        adapter = (int(req.adapter_slot)
                   if self.engine.adapters is not None else None)
        if self.paged is not None and req.kv_import is not None:
            seated = self._try_import(req, i)
            if seated is not None:
                return seated  # ("handoff", first_token)
            # payload landed in the radix as a prefix hint; the normal
            # paged admission below radix-hits it
        if self.paged is not None or self.engine.store is not None:
            # either layout's prefix reuse: the paged pool shares the
            # cached pages in place, the contiguous strips' side store
            # copies them in; only the rest of the prompt prefills
            if self.paged is not None:
                self.paged.priced[i] = self.page_commitment(req)
            admit = (self.engine.prefill_paged if self.paged is not None
                     else self.engine.prefill_stored)
            out = admit(self.params, self._cache, req.prompt, i,
                        sample=sample, adapter_id=adapter,
                        cache_salt=req.tenant)
            self._cache, logits, n, cached = out[:4]
            hidden = out[4] if rh else None
            self.prefill_dispatches += n
            with self._scratch_mu:
                self._last_prefill = {"dispatches": n,
                                      "cached_tokens": cached}
        elif len(prompt) > self.engine.prefill_chunk:
            # long prompt: fixed-width chunks straight into the slot —
            # O(1) compiled shapes in prompt length
            n_chunks = -(-len(prompt) // self.engine.prefill_chunk)
            out = self.engine.prefill_chunked(
                self.params, self._cache, prompt, i, sample=sample,
                adapter_id=adapter)
            self._cache, logits = out[:2]
            hidden = out[2] if rh else None
            self.prefill_dispatches += n_chunks
            with self._scratch_mu:
                self._last_prefill = {"dispatches": n_chunks}
        else:
            out = self.engine.prefill(self.params, prompt,
                                      sample=sample, adapter_id=adapter)
            kv, logits = out[:2]
            hidden = out[2] if rh else None
            self._cache = self.engine.insert(
                self._cache, kv, i, len(prompt))
            self.prefill_dispatches += 1
            with self._scratch_mu:
                self._last_prefill = {"dispatches": 1}
        if hidden is not None:
            # the prompt's last hidden state seeds the slot's drafting row
            self._hidden = self._hidden.at[i].set(jnp.asarray(hidden)[0])
        return logits

    def _try_import(self, req: Request, i: int):
        """Land ``req.kv_import``'s pages and, when the payload covers the
        FULL prompt with its first token, seat slot ``i`` ready to decode
        — the disaggregated handoff's zero-dispatch admission. Returns
        ``("handoff", first_token)`` on a seat, None when the payload is
        only a prefix hint (or pool pressure evicted part of the import
        before the slot could share it) — the caller then runs the normal
        paged admission, which radix-hits whatever survived. Idempotent
        under the dispatch retry: import skips chunks already cached and
        ``match_prefix`` releases any prior holdings first."""
        from picotron_tpu.inference.page_transport import TransportError
        from picotron_tpu.inference.paged_kv import PagePoolExhausted

        payload = req.kv_import
        self.paged.priced[i] = self.page_commitment(req)
        try:
            self._cache, info = self.engine.import_prefix(self._cache,
                                                          payload)
        except (TransportError, PagePoolExhausted) as e:
            # a payload this replica cannot land (corrupt/truncated bytes,
            # no pool room for the extra pages) must not cost the request:
            # it is perfectly servable by self-prefilling — the documented
            # degrade-to-colocated contract. The import released every
            # page it allocated, so the fallback starts clean.
            self.obs.registry.counter(
                "picotron_handoff_dropped_total",
                "kv payloads dropped as locally unusable").inc()
            log0(f"serving: kv import for {req.uid!r} dropped "
                 f"({type(e).__name__}: {e}); self-prefilling", flush=True)
            return None
        if info["pages_imported"] > 0:
            # counted on pages actually landing — a retried admission's
            # second import (everything already cached) must not inflate
            # the acceptance counter
            self._remote_hits_total.inc()
        ids = [int(t) for t in payload.get("token_ids") or []]
        first = payload.get("first_token")
        if first is None or ids != [int(t) for t in req.prompt]:
            return None
        cached = self.paged.match_prefix(i, ids, cap_last=False,
                                         salt=req.tenant)
        if cached != len(ids):
            return None
        self._cache = self.engine.seat_slot(self._cache, i, cached)
        if self._hidden is not None:
            # no prefill dispatch ran, so there is no hidden state for
            # the learned drafter's first round: zero the row rather
            # than draft from the PREVIOUS occupant's state (the first
            # verify re-seeds it; a garbage first draft is rejected by
            # verify either way — correctness never depends on this)
            self._hidden = self._hidden.at[i].set(0)
        with self._scratch_mu:
            self._last_prefill = {"dispatches": 0, "cached_tokens": cached,
                                  "imported_pages": info["pages_imported"]}
        self.handoff_seated += 1
        return ("handoff", int(first))

    def export_prefix(self, ids, first_token=None,
                      tenant: str = "") -> dict:
        """Serialize the longest radix-cached prefix of ``ids`` from this
        batcher's cache (the serve front end's /kv/export + /kv/pages
        surface — the caller serializes batcher access). ``tenant``
        scopes the lookup to that tenant's radix domain and rides in the
        payload."""
        return self.engine.export_prefix(self._cache, ids,
                                         first_token=first_token,
                                         cache_salt=tenant)

    def import_prefix(self, payload) -> dict:
        """Land a transport payload in this batcher's cache/radix (the
        /kv/import surface). Returns the import info dict."""
        self._cache, info = self.engine.import_prefix(self._cache, payload)
        if info["pages_imported"] > 0:
            # counted on pages actually landing — a retried admission's
            # second import (everything already cached) must not inflate
            # the acceptance counter
            self._remote_hits_total.inc()
        return info

    def _pick(self) -> int:
        """Index of the next admission candidate in the queue: the
        highest priority class first, FIFO within a class — except that
        a TTFT-SLO request jumps ahead of best-effort peers of its OWN
        class (its clock is already running; theirs is not)."""
        best = 0
        for j in range(1, len(self._pending)):
            r, b = self._pending[j], self._pending[best]
            if r.priority > b.priority:
                best = j
            elif (r.priority == b.priority and b.ttft_slo_ms is None
                  and r.ttft_slo_ms is not None):
                best = j
        return best

    def _prefill_gate(self, req: Request, tokens: Optional[int] = None,
                      submit_t: Optional[float] = None) -> bool:
        """SLO-aware chunked-prefill interleaving: when an ACTIVE slot
        carries a TPOT SLO, admission stops after one ``prefill_chunk``'s
        worth of prompt tokens per scheduler round — prefill work
        head-of-line blocks the decode dispatch behind it, and the round
        cap spreads that stall out so the decoders' token gaps stay near
        their target. The first admission of a round always passes
        (progress guarantee). A waiting request whose TTFT budget is
        half spent PREEMPTS the cap — its own SLO outranks the decoders'
        smoothness — with both decisions visible in the
        ``picotron_tenant_prefill_*`` counters.

        ``tokens`` prices the decision (default: the whole prompt — a
        serial admission prefills it all this round); the mixed lane
        feed prices ONE chunk, so the same gate budget becomes the lane
        feed rate. ``submit_t`` overrides the pending-queue clock lookup
        for the TTFT preempt (the lane's request left ``_submit_t`` at
        lane admission; its slot record carries the time instead)."""
        if tokens is None:
            tokens = len(req.prompt)
        if self._round_prefill_tokens == 0:
            return True
        if not any(s is not None and s.req.tpot_slo_ms is not None
                   for s in self._slots):
            return True
        if req.ttft_slo_ms is not None:
            t0 = (submit_t if submit_t is not None
                  else self._submit_t.get(req.uid))
            if (t0 is not None and (self._clock() - t0) * 1000.0
                    >= req.ttft_slo_ms / 2.0):
                self._tstat(req)["prefill_preempts"] += 1
                self.obs.registry.counter(
                    "picotron_tenant_prefill_preempts_total",
                    "TTFT-pressed admissions that preempted the "
                    "interleave cap, by tenant",
                    tenant=self._tname(req)).inc()
                return True
        if (self._round_prefill_tokens + tokens
                <= self.engine.prefill_chunk):
            return True
        self._tstat(req)["prefill_deferred"] += 1
        self.obs.registry.counter(
            "picotron_tenant_prefill_deferred_total",
            "admissions deferred a round by the TPOT interleave cap, "
            "by tenant",
            tenant=self._tname(req)).inc()
        return False

    def _lane_wants(self, req: Request, i: int) -> bool:
        """Whether ``req`` should prefill through slot ``i``'s shard lane
        instead of a blocking serial dispatch. Lane-worthy: a prompt
        longer than one chunk (the serial path would run the exact same
        chunk programs, just as solo stalls), or a paged prompt with a
        radix-cached prefix (the serial path resumes CHUNKED past it —
        again the lane's exact computation). A cold prompt at or under
        one chunk stays serial: its one-shot bucketed prefill is a
        different program family, and admitting it serially keeps the
        mixed-off bit-identity contract chunk-free paths rest on. A
        handoff payload (``kv_import``) stays serial too — its import
        path may seat the slot with zero prefill work."""
        if not self._mixed or req.kv_import is not None:
            return False
        if len(req.prompt) > self.engine.prefill_chunk:
            return True
        if self.paged is None:
            return False
        ids = [int(t) for t in req.prompt]
        if self.engine.dp_size > 1:
            return self.paged.peek_prefix(
                ids, salt=req.tenant,
                shard=i // self.engine.slots_per_shard) > 0
        return self.paged.peek_prefix(ids, salt=req.tenant) > 0

    def _admit(self) -> None:
        self._round_prefill_tokens = 0
        spb = self.engine.slots_per_shard
        order = range(len(self._slots))
        if self._mixed and self.engine.dp_size > 1:
            # feed lanes by the rebalance planner's occupancy view: free
            # slots on the least-occupied shard seat (and lane) first, so
            # the global queue drains toward the shard with headroom.
            # Request ADMISSION order is untouched (_pick per free slot),
            # so the per-admission key chain — and with it every stream —
            # is placement-independent.
            occ = self.shard_occupancy()
            order = sorted(range(len(self._slots)),
                           key=lambda x: (occ[x // spb], x))
        for i in order:
            if self._slots[i] is not None:
                continue
            skip_slot = False
            while True:
                if not self._pending:
                    return
                j = self._pick()
                req = self._pending[j]
                if self.paged is not None:
                    need = self.page_commitment(req)
                    if need > self.paged.usable_pages:
                        # can NEVER fit the pool: shed at the door
                        del self._pending[j]
                        self._submit_t.pop(req.uid, None)
                        self.counters["shed"] += 1
                        self._results[req.uid] = self._shed_result(req)
                        continue
                lane = self._lane_wants(req, i)
                if lane and self._lanes[i // spb] is not None:
                    # this shard's lane is mid-prompt: the candidate
                    # stays queued (FIFO head-of-line, like a gate
                    # deferral) — but a free slot on ANOTHER shard may
                    # still take it, so only this seat is skipped
                    skip_slot = True
                    break
                if self.paged is not None:
                    if not self.paged.can_admit(need, slot=i):
                        # transient pressure: wait — slots finishing
                        # return pages; admitting now could strand a
                        # live slot mid-decode
                        return
                if not lane and not self._prefill_gate(req):
                    return  # deferred to the next round's admission
                del self._pending[j]
                break
            if skip_slot:
                continue
            if lane:
                self._lane_start(req, i)
                continue
            submit_t = self._submit_t.pop(req.uid, None)
            root = self._req_spans.get(req.uid)
            t_admit = self._clock()
            if submit_t is not None:
                # the wait is over the moment the slot is assigned: the
                # span chain's first link, parented to the request root
                self.obs.tracer.record("queue_wait", submit_t, t_admit,
                                       parent=root)
            # the admit-time key: with the on-device epilogue it is drawn
            # BEFORE the dispatch (the program needs it as an operand);
            # host-side it is drawn after, exactly where it always was.
            # Either way it is the SAME link of the split chain — one
            # split per admit — so the two modes emit seeded-identical
            # streams (tests/test_sampling_epilogue.py pins this through
            # a full batcher run).
            fold = None
            if self._sched == "slot":
                # slot schedule: the one per-admit split seeds the slot's
                # BASE key; the first generated token sits at sequence
                # index len(prompt) and is keyed fold_in(base, index - 1)
                # like every later position (see _base_keys in __init__)
                self._base_keys[i] = np.asarray(self._split())
                fold = jax.random.fold_in(
                    jnp.asarray(self._base_keys[i]), len(req.prompt) - 1)
                key = fold if self.engine.sample_on_device else None
            else:
                key = (self._split() if self.engine.sample_on_device
                       else None)
            # every second this SOLO prefill dispatch runs is a second no
            # active decode slot advances — the interference the mixed
            # lane exists to remove. Timed whenever a decoder is parked
            # behind it (in both modes: the mixed-off baseline's stall
            # and the mixed-on residual are the A/B story).
            stall0 = (self._clock()
                      if any(s is not None and not s.prefilling
                             for s in self._slots) else None)
            try:
                pf_span = self.obs.tracer.begin(
                    "prefill", parent=root, uid=req.uid,
                    prompt_tokens=len(req.prompt))
                t_prefill = self._clock()
                logits = retry(lambda: self._prefill_into(req, i, key),
                               **self._retry)
                self.obs.tracer.end(pf_span, **self._last_prefill)
                if self._last_prefill.get("dispatches", 1) > 0:
                    self._prefill_tokens_total.inc(
                        len(req.prompt)
                        - self._last_prefill.get("cached_tokens", 0))
            except Exception as e:  # noqa: BLE001 - isolated to this request
                # the failure costs only THIS request: it never held a slot,
                # so release frees whatever partial prefill state landed and
                # everyone already admitted keeps decoding
                self.obs.tracer.end(pf_span, error=type(e).__name__)
                self.counters["admitted"] += 1
                self.counters["errored"] += 1
                self._tenant_count(req, "admitted")
                self._tenant_count(req, "errored")
                span = self._req_spans.pop(req.uid, None)
                if span is not None:
                    self.obs.tracer.end(span, finish_reason="error")
                self._results[req.uid] = GenerationResult(
                    req.uid, list(req.prompt), [], "error",
                    span_id=_sid(span))
                _log_dispatch_failure("prefill", req.uid, e)
                if self._cache_ok():
                    # free whatever partial prefill state landed in the slot
                    self._cache = self.engine.release(self._cache, i)
                else:
                    self._cache_lost()
                continue
            finally:
                if stall0 is not None:
                    self.obs.registry.histogram(
                        "picotron_decode_stall_seconds",
                        "decode time lost to a blocking solo prefill "
                        "dispatch, by tenant",
                        tenant=self._tname(req)).observe(
                            self._clock() - stall0)
            self.counters["admitted"] += 1
            self._tenant_count(req, "admitted")
            if self._last_prefill.get("dispatches", 1) > 0:
                # prompt tokens that actually prefilled this round (a
                # handoff seat or full radix hit costs the gate nothing)
                self._round_prefill_tokens += len(req.prompt)
            now = self._clock()
            deadline = (now + req.timeout_s
                        if req.timeout_s is not None else None)
            slot = _Slot(req, deadline=deadline, submit_t=submit_t)
            if submit_t is not None:
                # measured at the original point (post-prefill), so the
                # /statz percentile semantics are unchanged; the span
                # above ends at slot assignment (the actual queue time)
                slot.queue_wait_s = now - submit_t
                self._queue_wait_hist.observe(slot.queue_wait_s)
            self._slots[i] = slot
            # new occupant: bump the seat's epoch so an in-flight round
            # issued against the PREVIOUS occupant drops this row at sync
            self._epoch[i] += 1
            self._adapter[i] = (req.adapter_slot
                                if self.engine.adapters is not None else 0)
            # fresh request: the controller restarts the slot's policy
            # and stateful drafters drop any previous occupant's index
            if self.controller is not None:
                self.controller.reset(i, tpot_slo_s=(
                    req.tpot_slo_ms / 1000.0
                    if req.tpot_slo_ms is not None else None))
            for d in self._drafters.values():
                d.begin(req.uid)
            self._temp[i] = req.temperature
            self._top_k[i] = req.top_k
            self._top_p[i] = req.top_p
            self._eos[i] = req.eos_id if req.eos_id is not None else -1
            if self._blocks:
                # no token comes of the prefill: the prompt's remainder
                # leads the slot's first block, whose round emits the first
                rest = self._remainder(req.prompt)
                self._given[i, :rest] = req.prompt[len(req.prompt) - rest:]
                self._given_n[i] = rest
                continue
            if isinstance(logits, tuple) and logits[:1] == ("handoff",):
                # seated from an imported handoff: the prefill worker
                # already sampled the first token — nothing to draw here
                first = int(logits[1])
            elif self.engine.sample_on_device:
                # the dispatch already drew the first token (epilogue);
                # the one int crossing here is the whole logits payload
                first = int(np.asarray(logits).reshape(-1)[0])
            else:
                # slot schedule host-side: the folded per-position key
                # (categorical over the [1, V] row draws the same token
                # the device epilogue's [V] draw would — element count,
                # not shape, fixes the Gumbel draw)
                skey = fold if self._sched == "slot" else self._split()
                first = int(sampling.sample_jit(
                    logits, skey,
                    np.float32([req.temperature]),
                    np.int32([req.top_k]),
                    np.float32([req.top_p]))[0])
            if self._last_prefill.get("dispatches", 1) > 0:
                # the prefill programs are enqueued async; the first
                # token's arrival on the host is where their time ends
                self.engine.observe_dispatch("prefill",
                                             self._clock() - t_prefill)
            if self._overlap:
                # seed the device-carried last-token row for the seat
                # (round N+1's input): an in-flight round only reads it
                # through its snapshotted operand, so this patch is safe
                self._dev_last = self._dev_tok().at[i].set(first)
            self._tokens_done(i, [first])

    # ---- mixed prefill–decode dispatch (the fused lane) -------------------

    def _lane_start(self, req: Request, i: int) -> None:
        """Seat ``req`` in free slot ``i`` as a PREFILLING occupant and
        open its shard's lane: the prompt will flow through the fused
        dispatches one ``prefill_chunk`` at a time (``_lane_feed``), no
        solo prefill dispatch ever issued. Admission accounting (counters,
        queue-wait, epoch bump, sampling rows, controller/drafter resets)
        mirrors the serial seat; the first token — and with it TTFT and
        ``_tokens_done`` — arrives when the final chunk lands."""
        sh = i // self.engine.slots_per_shard
        submit_t = self._submit_t.pop(req.uid, None)
        root = self._req_spans.get(req.uid)
        t_admit = self._clock()
        if submit_t is not None:
            self.obs.tracer.record("queue_wait", submit_t, t_admit,
                                   parent=root)
        # the one per-admit split seeds the slot's base key exactly like
        # a serial admission (admission ORDER fixes the streams); the
        # final chunk's first-token draw folds at len(prompt) - 1 — the
        # same key every serial chunk's unconsumed epilogue uses
        self._base_keys[i] = np.asarray(self._split())
        fold = jax.random.fold_in(
            jnp.asarray(self._base_keys[i]), len(req.prompt) - 1)
        ids = [int(t) for t in req.prompt]
        cached = 0
        if self.paged is not None:
            self.paged.priced[i] = self.page_commitment(req)
            cached = self.paged.match_prefix(i, ids, salt=req.tenant)
            if cached > 0:
                # park the shared prefix ready to resume — the serial
                # path's radix-hit admission, minus its chunk dispatches
                self._cache = self.engine.seat_slot(self._cache, i,
                                                    cached)
        self.counters["admitted"] += 1
        self._tenant_count(req, "admitted")
        now = self._clock()
        deadline = (now + req.timeout_s
                    if req.timeout_s is not None else None)
        slot = _Slot(req, deadline=deadline, submit_t=submit_t,
                     prefilling=True)
        if submit_t is not None:
            slot.queue_wait_s = now - submit_t
            self._queue_wait_hist.observe(slot.queue_wait_s)
        self._slots[i] = slot
        self._epoch[i] += 1
        self._adapter[i] = (req.adapter_slot
                            if self.engine.adapters is not None else 0)
        if self.controller is not None:
            self.controller.reset(i, tpot_slo_s=(
                req.tpot_slo_ms / 1000.0
                if req.tpot_slo_ms is not None else None))
        for d in self._drafters.values():
            d.begin(req.uid)
        self._temp[i] = req.temperature
        self._top_k[i] = req.top_k
        self._top_p[i] = req.top_p
        self._eos[i] = req.eos_id if req.eos_id is not None else -1
        pf_span = self.obs.tracer.begin(
            "prefill", parent=root, uid=req.uid,
            prompt_tokens=len(req.prompt), lane=True)
        self._lanes[sh] = dict(
            slot=i, epoch=int(self._epoch[i]), req=req, ids=ids,
            cached=cached, done_end=cached, fed_end=cached, key=fold,
            chunks=0, span=pf_span, root=root)

    def _lane_drop(self, sh: int, reason: str) -> None:
        """Abandon shard ``sh``'s lane mid-prompt (occupant retired —
        timeout/error/cache loss): close its prefill span; the seat's
        epoch bump already isolates any chunk still in flight."""
        ln = self._lanes[sh]
        if ln is None:
            return
        self._lanes[sh] = None
        self.obs.tracer.end(ln["span"], error=reason,
                            dispatches=ln["chunks"],
                            cached_tokens=ln["cached"])
        self._prefill_tokens_total.inc(ln["done_end"] - ln["cached"])

    def _lane_feed(self) -> tuple:
        """Build this round's engine lane operands from the per-shard
        lane records: one next chunk per live lane, gated by the SAME
        per-round token budget serial admissions pay (``_prefill_gate``
        with the chunk's size — the gate budget IS the lane feed rate,
        deferred chunks count ``prefill_deferred`` exactly like deferred
        admissions). Returns (lanes-or-None for ``engine.decode_block``
        / ``verify``, feed records for ``_lane_land``). Under overlap a
        lane feeds one chunk ahead of its last CONFIRMED row
        (``fed_end`` > ``done_end``): the in-flight round's chunk is
        sequenced on device by the cache donation chain, so the next
        chunk's rows are already parked when this one executes."""
        if not self._mixed:
            return None, ()
        C = self.engine.prefill_chunk
        lanes: list = [None] * self.engine.dp_size
        feeds: list = []
        for sh in range(self.engine.dp_size):
            ln = self._lanes[sh]
            if ln is None:
                continue
            i = ln["slot"]
            s = self._slots[i]
            if (s is None or s.req is not ln["req"]
                    or self._epoch[i] != ln["epoch"]):
                self._lane_drop(sh, "occupant_retired")
                continue
            ids = ln["ids"]
            s0 = ln["fed_end"]
            if s0 >= len(ids):
                continue  # final chunk in flight, waiting to land
            end = min(s0 + C, len(ids))
            if not self._prefill_gate(s.req, tokens=end - s0,
                                      submit_t=s.submit_t):
                continue  # deferred a round; gate counters already bumped
            if self.paged is not None:
                # absolute chunk start (the paged scatter has no clamp
                # hazard; a slid window would pointlessly COW a shared
                # prefix) — prefill_chunked's exact convention
                w0 = s0
            else:
                # contiguous window slide: past max_seq_len - C the
                # window backs up and re-feeds overlap tokens whose rows
                # recompute to the values already parked there
                w0 = min(s0, self.engine.max_seq_len - C)
            entry = dict(slot=i, tokens=ids[w0:end], start=w0)
            if self.engine.sample_on_device:
                entry.update(key=np.asarray(ln["key"]),
                             temperature=s.req.temperature,
                             top_k=s.req.top_k, top_p=s.req.top_p)
            if self.engine.adapters is not None:
                entry["adapter"] = int(s.req.adapter_slot)
            lanes[sh] = entry
            self._round_prefill_tokens += end - s0
            self.obs.registry.counter(
                "picotron_prefill_lane_tokens_total",
                "prompt tokens prefilled through the fused lane, "
                "by tenant",
                tenant=self._tname(s.req)).inc(end - s0)
            ln["fed_end"] = end
            feeds.append(dict(shard=sh, lane=ln, s0=s0, end=end,
                              t0=self._clock()))
        if not any(e is not None for e in lanes):
            return None, feeds
        return lanes, feeds

    def _lane_land(self, feeds) -> None:
        """Deliver one round's lane results: confirm each fed chunk
        (paged host length, ``lane`` span, dispatch accounting) and, on
        a prompt's FINAL chunk, draw/record the first token — the
        ``_tokens_done`` seat flip that turns the prefilling occupant
        into a decoder next round. ``_lane_scratch`` holds the round's
        (lane_out, lane_hid); a round that never delivered (all-failed
        isolation) rewinds ``fed_end`` so the chunk re-feeds — its
        rewrite is byte-identical, so a retried chunk costs nothing but
        the dispatch."""
        scratch, self._lane_scratch = self._lane_scratch, None
        if not feeds:
            return
        if scratch is None:
            for f in feeds:
                ln = f["lane"]
                if self._lanes[f["shard"]] is ln:
                    ln["fed_end"] = ln["done_end"]
            return
        lane_out, lane_hid = scratch
        for f in feeds:
            sh, ln = f["shard"], f["lane"]
            if self._lanes[sh] is not ln:
                continue  # dropped while the chunk flew
            i = ln["slot"]
            s = self._slots[i]
            if s is None or self._epoch[i] != ln["epoch"]:
                self._lane_drop(sh, "occupant_retired")
                continue
            self.prefill_dispatches += 1
            ln["chunks"] += 1
            ln["done_end"] = f["end"]
            if self.paged is not None:
                self.paged.set_len(i, f["end"])
            t1 = self._clock()
            self.obs.tracer.record(
                "lane", f["t0"], t1, parent=ln["root"],
                chunk=ln["chunks"], start=f["s0"], end=f["end"],
                slot=i)
            if f["end"] < len(ln["ids"]):
                continue  # mid-prompt: more chunks to feed
            # final chunk: the fused epilogue's draw (or logits row) is
            # this prompt's first token — the serial _prefill_into tail
            req = s.req
            if self.engine.sample_on_device:
                first = int(np.asarray(lane_out)[sh])
            else:
                row = np.asarray(lane_out)[sh]
                first = int(sampling.sample_jit(
                    row[None, :], ln["key"],
                    np.float32([req.temperature]),
                    np.int32([req.top_k]),
                    np.float32([req.top_p]))[0])
            if self._hidden is not None and lane_hid is not None:
                self._hidden = self._hidden.at[i].set(
                    jnp.asarray(lane_hid)[sh])
            if self.paged is not None:
                self.paged.register_prompt(i, ln["ids"], salt=req.tenant)
            with self._scratch_mu:
                self._last_prefill = {"dispatches": ln["chunks"],
                                      "cached_tokens": ln["cached"],
                                      "lane": True}
            self.obs.tracer.end(ln["span"], dispatches=ln["chunks"],
                                cached_tokens=ln["cached"], lane=True)
            self._prefill_tokens_total.inc(len(ln["ids"]) - ln["cached"])
            self._lanes[sh] = None
            s.prefilling = False
            if self._overlap:
                # seed the device-carried last-token row (round N+1's
                # input) exactly like a serial admission's seat patch
                self._dev_last = self._dev_tok().at[i].set(first)
            self._tokens_done(i, [first])

    # dp rebalance discipline (the fleet controller's hysteresis/cooloff
    # shape, applied to slot placement): act only past a real skew, then
    # sit out a few rounds so admission/retirement churn settles before
    # the next move — a planner that can never thrash
    REBALANCE_WATERMARK = 2  # min (max - min) shard occupancy skew
    REBALANCE_COOLOFF = 4    # scheduler rounds to sit out after a move

    def shard_occupancy(self) -> list:
        """Occupied-slot count per dp shard, computed HOST-SIDE from the
        slot list — never from a traced value inside the jitted dispatch
        (reading a device occupancy count there would host-sync the hot
        path: exactly picolint PICO-J001's hazard). dp=1 returns one
        entry covering every slot."""
        occ = [0] * self.engine.dp_size
        for i, s in enumerate(self._slots):
            if s is not None:
                occ[i // self.engine.slots_per_shard] += 1
        return occ

    def _rebalance(self) -> None:
        """Migrate ONE parked slot's KV pages from the most- to the
        least-occupied dp shard when the occupancy skew crosses the
        watermark — through ``engine.migrate_slot`` (the page-transport
        device path: byte-exact, refcount-correct, radix re-grafted on
        the destination shard), then move the slot's host rows and sit
        out the cooloff. An aborted migration (destination pool
        exhausted, dispatch fault) leaves the source slot serving
        untouched and still starts the cooloff — pressure that failed a
        move now will fail it next round too."""
        if (self.engine.dp_size <= 1 or self.paged is None):
            return
        if self._rebalance_cooloff > 0:
            self._rebalance_cooloff -= 1
            return
        occ = self.shard_occupancy()
        hi = max(range(len(occ)), key=lambda x: occ[x])
        lo = min(range(len(occ)), key=lambda x: occ[x])
        if occ[hi] - occ[lo] < self.REBALANCE_WATERMARK:
            return
        spb = self.engine.slots_per_shard
        # a prefilling occupant never migrates: its lane record pins the
        # slot to its shard and its host length trails the fed chunks
        src = next((i for i in range(hi * spb, (hi + 1) * spb)
                    if self._slots[i] is not None
                    and not self._slots[i].prefilling), None)
        dst = next((i for i in range(lo * spb, (lo + 1) * spb)
                    if self._slots[i] is None), None)
        if src is None or dst is None:
            return
        s = self._slots[src]
        try:
            self._cache, moved = self.engine.migrate_slot(
                self._cache, src, dst, prompt_ids=s.req.prompt,
                cache_salt=s.req.tenant)
        except Exception:  # noqa: BLE001 - planned abort, slot unharmed
            # all-or-nothing inside migrate_slot (PagePoolExhausted on a
            # full destination shard, or a dispatch fault caught before
            # the donating write): the source slot is still serving from
            # where it was; just record and back off
            self.obs.registry.counter(
                "picotron_slot_migrations_total",
                "cross-shard slot migrations by outcome",
                outcome="aborted").inc()
            self._rebalance_cooloff = self.REBALANCE_COOLOFF
            return
        # the request follows its pages: every per-slot host row moves to
        # dst and src returns to the _finish free-slot defaults
        self._slots[dst], self._slots[src] = s, None
        for arr in (self._last_tok, self._temp, self._top_k, self._top_p,
                    self._eos, self._budget, self._adapter):
            arr[dst] = arr[src]
        self._last_tok[src] = 0
        self._temp[src] = 0.0
        self._top_k[src] = 0
        self._top_p[src] = 1.0
        self._eos[src] = -1
        self._budget[src] = 0
        self._adapter[src] = 0
        # the slot-schedule base follows the request (its key stream is
        # placement-independent), and both seats change occupant — any
        # in-flight rows for either drop at sync (the overlap path drains
        # before planning a move, so this is belt and braces)
        self._base_keys[dst] = self._base_keys[src]
        self._base_keys[src] = 0
        self._epoch[src] += 1
        self._epoch[dst] += 1
        if self._dev_last is not None:
            self._dev_last = (self._dev_last.at[dst]
                              .set(self._dev_last[src]).at[src].set(0))
        if self._hidden is not None:
            self._hidden = (self._hidden.at[dst].set(self._hidden[src])
                            .at[src].set(0))
        if self.controller is not None:
            # the policy restarts on the destination (its latency stats
            # were per-placement anyway); the vacated slot goes clean
            self.controller.reset(dst, tpot_slo_s=(
                s.req.tpot_slo_ms / 1000.0
                if s.req.tpot_slo_ms is not None else None))
            self.controller.reset(src)
        self.rebalance_count += 1
        self.rebalance_bytes += moved
        self.obs.registry.counter(
            "picotron_slot_migrations_total",
            "cross-shard slot migrations by outcome",
            outcome="ok").inc()
        self._rebalance_cooloff = self.REBALANCE_COOLOFF

    def _expire_deadlines(self) -> None:
        """Retire every slot past its deadline with reason "timeout" — the
        slot frees immediately, so a stuck or over-budget request cannot
        starve the queue behind it. Runs FIRST in each scheduler round
        (before admission), so a slot freed by a timeout is refilled in the
        same round instead of idling one full block."""
        now = self._clock()
        for i, s in enumerate(self._slots):
            if s is not None and s.deadline is not None and now >= s.deadline:
                self._finish(i, "timeout")

    def _plan_spec(self):
        """Per-slot draft lengths + drafter kinds for the next round, or
        (None, None) when the controller has turned EVERY occupied slot
        off — the batcher then falls back to a blocked decode round
        (speculation out of the way entirely, not a 0-draft verify)."""
        n = len(self._slots)
        lens = np.zeros(n, np.int32)
        kinds: list = [None] * n
        occupied = [i for i, s in enumerate(self._slots) if s is not None]
        if self.controller is not None:
            clens = self.controller.lens()
            ckinds = self.controller.drafter_kinds()
            for i in occupied:
                lens[i] = clens[i]
                kinds[i] = ckinds[i]
            if occupied and not lens.any():
                return None, None
        else:
            for i in occupied:
                lens[i] = self.engine.spec_len
                kinds[i] = self.drafter.kind
        return lens, kinds

    def _merge_hidden(self, hid, counts) -> None:
        """Fold one dispatch's hidden states into the per-slot device
        rows: only slots that produced tokens this dispatch advance (a
        solo isolation re-dispatch merges exactly its own row)."""
        if self._hidden is not None and hid is not None:
            self._hidden = jnp.where(
                jnp.asarray(np.asarray(counts) > 0)[:, None],
                hid, self._hidden)

    def step(self) -> None:
        """Expire overdue slots, admit waiting requests into free slots,
        then advance every occupied slot by one decode block (up to
        ``engine.decode_block_len`` tokens per slot, one dispatch) — or,
        on a speculative engine, by one draft-verify dispatch (1 to
        ``engine.spec_len + 1`` tokens per slot; with the controller, a
        RAGGED dispatch at each slot's own draft length, or the blocked-
        decode fallback once every slot's speculation is off). A dispatch
        failure that survives the retry budget is isolated to the slots
        that fail alone (see module docstring) — step() itself never
        raises for an engine-side fault.

        With ``inference.overlap`` the round runs PIPELINED instead: see
        ``_step_overlap`` (issue round N+1, then drain round N).

        Either way the round's wall time is tiled into phases
        (``self._phases``): plan until the issue (less admit), issue,
        sync, deliver from the sync's end on."""
        self._phases.to("step/plan")
        try:
            if self._overlap:
                self._step_overlap()
            else:
                self._step_serial()
        finally:
            self._phases.close(self._round_seq, self._round_facts)

    def _round_facts(self) -> dict:
        """What a slow round's record says of the batcher (asked for only
        when the stall judge keeps one)."""
        return {"live_slots": sum(s is not None for s in self._slots),
                "prompt_tokens": self._round_prefill_tokens}

    def _admit_phase(self) -> None:
        """``_admit`` as its phase, judged under what it dispatched: the
        prefill programs and the prompt rows they ran (``admit_key``)."""
        self._phases.to("step/admit")
        n0 = self.prefill_dispatches
        rows0 = self._prefill_tokens_total.value
        self._admit()
        n = self.prefill_dispatches - n0
        self._phases.key("step/admit", admit_key(
            n, self._prefill_tokens_total.value - rows0 if n else 0))
        self._phases.to("step/plan")

    def _step_serial(self) -> None:
        self._expire_deadlines()
        self._rebalance()
        self._admit_phase()
        if not any(s is not None for s in self._slots):
            return
        for i, s in enumerate(self._slots):
            # a lane occupant rides the dispatch INACTIVE until its
            # final chunk lands (budget 0 — its ghost row is overwritten
            # by the lane chunk inside the same trace)
            self._budget[i] = (self._remaining(i)
                               if s is not None and not s.prefilling
                               else 0)
        budget = self._budget.copy()
        lanes, feeds = self._lane_feed()
        self._lane_scratch = None
        t_round = self._clock()
        spec_lens = spec_kinds = None
        if self.engine.spec_len > 0:
            spec_lens, spec_kinds = self._plan_spec()
        if spec_lens is not None:
            toks, counts, failed = self._spec_round(budget, spec_lens,
                                                    spec_kinds,
                                                    lanes=lanes)
        else:
            if self._sched == "slot":
                # per-slot bases: the program folds each row's position
                # in-trace, so the operand is round-count-independent
                keys = self._base_keys
            else:
                # one program advances the chain by the block's links and
                # the keys stay on the device: nothing here waits for it
                self._key, keys = self.engine.round_keys(self._key)
            kind = _judged_as("decode", feeds)

            def dispatch(b):
                self._phases.to("step/issue", kind)
                t0 = self._clock()
                self._note_issue(t0)
                res = self.engine.decode_block(
                    self.params, self._cache,
                    self._given if self._blocks else self._last_tok, keys,
                    self._eos, b, self._temp, self._top_k, self._top_p,
                    adapter_ids=(self._adapter if self.engine.adapters
                                 is not None else None), lanes=lanes,
                    given=self._given_n if self._blocks else None,
                    waiting=self._waiting if self._blocks else None)
                # the fused lane's outputs wait for _lane_land, after the
                # round delivers. An isolation re-dispatch re-runs the
                # lane chunk too: same rows, same bytes, so restashing is
                # idempotent. The slot schedule's next_tok feeds the
                # overlap pipeline; this synchronous path ignores it
                # (_last_tok, updated by the walk, stays authoritative)
                self._cache, self._lane_scratch = res.cache, res.lane
                self.decode_dispatches += 1
                self._phases.to("step/sync", kind)
                t_sync = self._clock()
                out = self._sync_outputs(res)
                self._merge_hidden(res.hidden, out[1])
                t1 = self._clock()
                self._phases.to("step/deliver")
                self._count_model_stats()
                dt_sync = t1 - t_sync
                with self._scratch_mu:
                    self._host_sync_s = dt_sync
                self._note_sync_end(t0, t1)
                self.engine.observe_dispatch("decode", t1 - t0)
                self.obs.tracer.record(
                    "dispatch/decode", t0, t1,
                    slots=int(np.count_nonzero(np.asarray(b) > 0)),
                    host_sync_s=round(dt_sync, 6))
                return out

            toks, counts, _, failed = self._guarded_round(dispatch, budget)
            self._slot_spans("decode", t_round, budget, counts, failed)
        for i, s in enumerate(self._slots):
            if s is not None and budget[i] > 0 and i not in failed:
                s.dispatches += 1
                if self._blocks:
                    self._round_of_blocks_done(i, toks[i], int(counts[i]))
                if self.controller is not None:
                    # policy tick AFTER this round's counters landed in
                    # the registry; idle slots advance their cooloff
                    self.controller.after_round(i)
        for i in failed:
            if self._slots[i] is not None:
                self._finish(i, "error")
        self._deliver_round(toks, counts)
        self._lane_land(feeds)

    def _round_of_blocks_done(self, i: int, toks, n: int) -> None:
        """What a round of blocks leaves slot ``i`` to hand back: it took
        the prompt's remainder, and its last block waits (``_waiting``) if
        the round ran to its end for the slot, ``n`` tokens behind the given
        ones making whole blocks: the last ``block_length`` of them. A round
        cut short ended the stream, whose last block nobody reads (and
        ``_retire`` clears what a stream that ends on a round's last token
        leaves here)."""
        run = self._given[i, :self._given_n[i]].tolist() + toks[:n].tolist()
        self._given_n[i] = 0
        Bd = self._given.shape[1]
        self._waiting[i] = run[-Bd:] \
            if len(run) == self.engine.decode_block_len else -1

    # ---- overlapped (zero-bubble) scheduling ------------------------------

    def _count_model_stats(self) -> None:
        """Add what the model's layers counted since the last round (this
        round's decode block, and the prefills admitted before it) to the
        registry: ``picotron_<name>_total`` for each of the engine's
        ``stat_names`` (docs/OBSERVABILITY.md). A block that does not
        count costs one attribute read."""
        stats = self.engine.take_stats()
        if stats is None:
            return
        for counter, n in zip(self._model_counters, stats):
            counter.inc(float(n))

    def _note_issue(self, t0: float) -> None:
        """Record the issue-to-issue scheduling gap: host time between
        the previous round's sync end and this issue — the bubble overlap
        exists to close. While a round is still in flight at issue the
        pipeline is gapless by construction (0.0). Feeds the
        picotron_dispatch_gap_seconds histogram and /statz ``overlap``.
        Every issue, serial or pipelined, takes the next ``_round_seq``."""
        self._round_seq += 1
        if self._ov_t0 is None:
            self._ov_t0 = t0
        if self._inflight is not None:
            gap = 0.0
        elif self._t_last_sync_end is None:
            return  # first round: nothing to gap against
        else:
            gap = max(0.0, t0 - self._t_last_sync_end)
        self._gap_hist.observe(gap)

    def _sync_outputs(self, res) -> tuple:
        """A round's outputs as host arrays, (tokens, counts, accepted or
        None), in the two parts of ``step/sync``: ``sync/wait``, blocked
        until the device has them (the launch and the program's run), and
        ``sync/fetch``, the read of ``res.packed``'s ONE copy to the host,
        which the engine asked for at issue, so it left with the program's
        end. What a sync site does after them (the learned drafter's hidden
        rows merged, the deferred page-table advance) is the phase's own
        time."""
        with self.obs.part("sync/wait"):
            jax.block_until_ready(res.packed)
        with self.obs.part("sync/fetch"):
            self.engine.count_copies("d2h")
            return res.host()

    def _note_sync_end(self, t_issue: float, t_end: float) -> None:
        self._t_last_sync_end = t_end
        self._ov_device_s += max(0.0, t_end - t_issue)
        self._ov_t1 = t_end

    def _step_overlap(self) -> None:
        """One PIPELINED scheduler round (``inference.overlap``): issue
        round N's dispatch before draining round N-1, so token delivery,
        finish detection, drafting, and admission all run while the
        device executes.

            expire -> rebalance -> admit -> issue N -> drain N-1

        Everything host-side sees state that is one round stale — budgets
        may overshoot (the device stops at EOS on its own and the walk
        truncates at the host rules), drafts guess from the previous
        round's tokens (sample-and-match acceptance makes the emitted
        stream independent of the guesses), and controller/admission
        decisions land one round late. A slot that finishes while a round
        is in flight bumps its seat epoch, so the drain drops its rows —
        exactly-once delivery; its KV overshoot dies with the released
        pages under the same length-pointer discipline verify overshoot
        always used. With no occupied slots the in-flight round drains
        and the pipeline empties (serve.py's shutdown loop relies on
        ``busy`` covering the in-flight record)."""
        self._expire_deadlines()
        self._rebalance_overlap()
        self._admit_phase()
        if not any(s is not None for s in self._slots):
            self._sync_inflight()
            return
        for i, s in enumerate(self._slots):
            # a lane occupant rides the dispatch INACTIVE until its
            # final chunk lands (budget 0 — its ghost row is overwritten
            # by the lane chunk inside the same trace)
            self._budget[i] = (self._remaining(i)
                               if s is not None and not s.prefilling
                               else 0)
        budget = self._budget.copy()
        rec = self._issue_round(budget)
        self._sync_inflight(next_t0=None if rec is None else rec["t0"])
        self._inflight = rec

    def _issue_round(self, budget):
        """Build and ISSUE one decode/verify dispatch without touching its
        results: every output stays an async future in the returned
        in-flight record (drained by ``_sync_inflight``). The input tokens
        come from the device-carried last-token row and the keys from the
        per-slot bases, so nothing here waits on the round before it. An
        issue-time failure (trace error, chaos hook) drains the pipeline
        and re-runs the SAME built inputs through the legacy guarded path
        (retry, then per-slot isolation) — returns None after delivering
        synchronously."""
        t_round = self._clock()
        lead = (None if self._inflight is None
                else self._inflight.get("lead"))
        # the lane rides the in-flight round and lands one round later
        # at sync, exactly like admissions already do: a chunk fed here
        # executes after the previous round's chunk (the cache donation
        # chain sequences them), so fed_end may lead done_end by one
        lanes, feeds = self._lane_feed()
        spec_lens = spec_kinds = None
        if self.engine.spec_len > 0:
            spec_lens, spec_kinds = self._plan_spec()
        adapter = (self._adapter if self.engine.adapters is not None
                   else None)
        if spec_lens is None:
            kind = "decode"
            nwrite = self.engine.decode_block_len

            def issue(b, toks_in):
                return self.engine.decode_block(
                    self.params, self._cache, toks_in, self._base_keys,
                    self._eos, b, self._temp, self._top_k, self._top_p,
                    adapter_ids=adapter, lead=lead, lanes=lanes)
        else:
            kind = "verify"
            nwrite = self.engine.spec_len + 1
            # drafting INSIDE the device-busy window, from one-round-stale
            # host state; column 0 is overridden by the device token row
            tokens = self._draft(spec_lens, spec_kinds)
            drafts = jnp.asarray(tokens[:, 1:])
            self.engine.count_copies("h2d")  # the round's second copy up

            def issue(b, toks_in):
                dev_tokens = jnp.concatenate(
                    [toks_in[:, None].astype(jnp.int32), drafts], axis=1)
                return self.engine.verify(
                    self.params, self._cache, dev_tokens, self._base_keys,
                    self._eos, b, self._temp, self._top_k, self._top_p,
                    draft_len=spec_lens, adapter_ids=adapter, lead=lead,
                    lanes=lanes)
        judged = _judged_as(kind, feeds)
        self._phases.to("step/issue", judged)
        t0 = self._clock()
        self._note_issue(t0)
        epochs = self._epoch.copy()
        try:
            out = issue(budget, self._dev_tok())
        except Exception as e:  # noqa: BLE001 - recovered synchronously
            _log_dispatch_failure("issue", "active slots", e)
            self._sync_inflight()
            self._round_fallback(kind, t_round, budget, spec_lens,
                                 spec_kinds, issue, feeds=feeds)
            return None
        self._cache, self._dev_last = out.cache, out.next_tok
        self.decode_dispatches += 1
        self._phases.to("step/plan")  # until the drain's sync claims it
        return dict(kind=kind, judged=judged, t_round=t_round, t0=t0,
                    budget=budget, epochs=epochs, res=out, hid=out.hidden,
                    spec_lens=spec_lens, spec_kinds=spec_kinds,
                    # lane futures + feed records: the sync stage lands
                    # them after the round's outputs materialize
                    lane=out.lane, feeds=feeds,
                    # the NEXT issue's _pre_write reach: this round may
                    # advance each slot by up to lead rows before the
                    # stale host_len catches up at sync
                    lead=np.minimum(np.maximum(budget, 0), nwrite),
                    seq=self._round_seq)

    def _round_fallback(self, kind, t_round, budget, spec_lens,
                        spec_kinds, issue, feeds=()) -> None:
        """Issue-time failure recovery: the pipeline is already drained
        (host state is current again), so re-run the round's built inputs
        through ``_guarded_round`` — the legacy retry/isolation semantics,
        transient chaos faults absorbed identically — and deliver
        synchronously like a non-overlapped step. Budget rows of seats
        freed by the drain are masked (their occupants are gone; a stale
        row would generate into a released seat). ``feeds`` are the
        failed issue's lane feed records: the ``issue`` closure carries
        their chunk operands, so the re-dispatch advances the lane too
        (byte-identical rewrite under isolation) and the shared land
        stage confirms or rewinds it."""
        occ = np.array([s is not None for s in self._slots])
        budget = np.where(occ, budget, 0).astype(budget.dtype)
        g = self.engine.spec_len
        self._lane_scratch = None

        judged = _judged_as(kind, feeds)

        def dispatch(b):
            self._phases.to("step/issue", judged)
            t0 = self._clock()
            self._note_issue(t0)
            res = issue(b, self._dev_tok())
            self._cache, self._lane_scratch = res.cache, res.lane
            self._dev_last = res.next_tok
            self.decode_dispatches += 1
            self._phases.to("step/sync", judged)
            t_sync = self._clock()
            outs = self._sync_outputs(res)
            # deferred page-table advance (engine.defer_advance): lands
            # here per successful dispatch, so isolation re-dispatches
            # compose exactly like the legacy per-dispatch advance
            self.engine.apply_advance(outs[1])
            self._merge_hidden(res.hidden, outs[1])
            t1 = self._clock()
            self._phases.to("step/deliver")
            dt_sync = t1 - t_sync
            with self._scratch_mu:
                self._host_sync_s = dt_sync
            self._note_sync_end(t0, t1)
            self.engine.observe_dispatch(kind, t1 - t0)
            args = dict(slots=int(np.count_nonzero(np.asarray(b) > 0)),
                        host_sync_s=round(dt_sync, 6))
            if kind == "verify":
                args["draft_len"] = g
            self.obs.tracer.record("dispatch/" + kind, t0, t1, **args)
            return outs

        toks, counts, accepted, failed = self._guarded_round(dispatch,
                                                             budget)
        extra = None
        if kind == "verify":
            self._spec_account(spec_lens, spec_kinds, accepted, budget,
                               failed)
            extra = (lambda i: {
                "draft_len": int(spec_lens[i]),
                "accepted": (int(accepted[i])
                             if accepted is not None else 0)})
        self._slot_spans(kind, t_round, budget, counts, failed,
                         extra=extra)
        for i, s in enumerate(self._slots):
            if s is not None and budget[i] > 0 and i not in failed:
                s.dispatches += 1
                if self.controller is not None:
                    self.controller.after_round(i)
        for i in failed:
            if self._slots[i] is not None:
                self._finish(i, "error")
        self._deliver_round(toks, counts)
        self._lane_land(feeds)

    def _sync_inflight(self, next_t0=None) -> None:
        """Drain the in-flight round: materialize its device outputs (the
        ONLY blocking sync on the overlap hot path), drop every row whose
        seat epoch moved since issue (late stop, re-seat — the
        exactly-once guarantee), apply the deferred page-table advance
        for the surviving rows, then deliver exactly like the legacy
        tail. ``next_t0`` is the just-issued round's issue time: when the
        drain ends after it, the window in between is recorded as an
        ``overlap`` span parented to this round's dispatch span (the
        chain tools/trace_dump.py validates)."""
        rec, self._inflight = self._inflight, None
        if rec is None:
            return
        kind = rec["kind"]
        self._phases.to("step/sync", rec["judged"])
        t_sync = self._clock()
        try:
            toks, counts, accepted = self._sync_outputs(rec["res"])
        except Exception as e:  # noqa: BLE001 - device-side round failure
            _log_dispatch_failure("sync", "in-flight round", e)
            if not self._cache_ok():
                self._cache_lost()
                return
            # outputs unrecoverable but the cache survived: the round's
            # slots retire like a failed dispatch's would. The lane's
            # chunk outputs are equally unrecoverable — rewind its feed
            # so the chunk re-runs (a byte-identical rewrite; the ghost
            # row an interim round writes past the stale length lands
            # masked, NULL-paged, or overwritten by the refeed).
            for f in rec.get("feeds") or ():
                ln = f["lane"]
                if self._lanes[f["shard"]] is ln:
                    ln["fed_end"] = ln["done_end"]
            for i in range(len(self._slots)):
                if (self._slots[i] is not None and rec["budget"][i] > 0
                        and rec["epochs"][i] == self._epoch[i]):
                    self._finish(i, "error")
            return
        t1 = self._clock()
        self._phases.to("step/deliver")
        dt_sync = t1 - t_sync
        with self._scratch_mu:
            self._host_sync_s = dt_sync
        self._note_sync_end(rec["t0"], t1)
        live = ((rec["epochs"] == self._epoch)
                & np.array([s is not None for s in self._slots]))
        counts = np.where(live, counts, 0)
        mbud = np.where(live, rec["budget"], 0)
        self.engine.apply_advance(counts)
        self._merge_hidden(rec["hid"], counts)
        self.engine.observe_dispatch(kind, t1 - rec["t0"])
        args = dict(round=rec["seq"],
                    slots=int(np.count_nonzero(
                        np.asarray(rec["budget"]) > 0)),
                    host_sync_s=round(dt_sync, 6))
        if kind == "verify":
            args["draft_len"] = self.engine.spec_len
        span = self.obs.tracer.record("dispatch/" + kind,
                                      rec["t0"], t1, **args)
        if next_t0 is not None and next_t0 < t1:
            # the zero-bubble witness: round seq's sync/deliver stage ran
            # while round seq+1 executed on device
            self.obs.tracer.record("overlap", next_t0, t1, parent=span,
                                   round=rec["seq"], over=rec["seq"] + 1)
        extra = None
        if kind == "verify":
            self._spec_account(rec["spec_lens"], rec["spec_kinds"],
                               accepted, mbud, ())
            extra = (lambda i: {
                "draft_len": int(rec["spec_lens"][i]),
                "accepted": (int(accepted[i])
                             if accepted is not None else 0)})
        self._slot_spans(kind, rec["t_round"], mbud, counts, (),
                         extra=extra)
        for i, s in enumerate(self._slots):
            if s is not None and mbud[i] > 0:
                s.dispatches += 1
                if self.controller is not None:
                    self.controller.after_round(i)
        self._deliver_round(toks, counts)
        if rec.get("feeds"):
            # the round's lane chunk lands with its outputs: confirmed
            # host lengths, lane span, and — on the final chunk — the
            # first token, one round after it was fed (like admissions)
            self._lane_scratch = rec["lane"]
            self._lane_land(rec["feeds"])

    def _rebalance_overlap(self) -> None:
        """dp rebalance under overlap: the migration planner reads the
        allocator's HOST view (host_len, page tables), which lags the
        in-flight round — so the pipeline drains before a move is
        planned, and only when the cheap host-side skew checks say one
        would actually happen."""
        if self.engine.dp_size <= 1 or self.paged is None:
            return
        if self._rebalance_cooloff > 0:
            self._rebalance()  # just the cooloff decrement — no drain
            return
        occ = self.shard_occupancy()
        if max(occ) - min(occ) < self.REBALANCE_WATERMARK:
            return
        self._sync_inflight()
        self._rebalance()

    def _slot_spans(self, kind: str, t0: float, budget, counts,
                    failed, extra=None) -> None:
        """Mirror one dispatch round into a child span PER REQUEST (the
        shared engine dispatch serves many slots; Chrome traces have no
        multi-parent events, so each request's chain gets its own copy of
        the round window). ``extra(i) -> dict`` adds per-slot args (the
        verify round's draft/accept counts)."""
        t1 = self._clock()
        for i, s in enumerate(self._slots):
            if s is None or budget[i] <= 0:
                continue
            args = {"tokens": int(counts[i])}
            if i in failed:
                args["error"] = "dispatch_failed"
            if extra is not None:
                args.update(extra(i))
            self.obs.tracer.record(kind, t0, t1,
                                   parent=self._req_spans.get(s.req.uid),
                                   **args)

    # ---- dispatch fault recovery ------------------------------------------

    def _cache_ok(self) -> bool:
        """Whether the cache's buffers are still live (a dispatch that
        failed DURING execution consumed the donated cache; one that failed
        before — hook faults, trace/compile errors — did not)."""
        lengths = self._cache["lengths"]
        return not (hasattr(lengths, "is_deleted") and lengths.is_deleted())

    def _cache_lost(self) -> None:
        """The donated cache was consumed by a failed dispatch: every
        parked sequence's K/V is gone, so every occupied slot finishes
        ``"error"`` and a fresh cache is built — the batcher (and its
        queue) outlives the fault even when isolation is impossible."""
        self._cache = self.engine.init_cache()
        # any in-flight round consumed the same dead buffers; its record
        # and the device-carried token row die with them (the _finish
        # epoch bumps below already mask its rows, this just drops the
        # references so the drain is a no-op)
        self._inflight = None
        self._dev_last = None
        for i, s in enumerate(self._slots):
            if s is not None:
                self._finish(i, "error")
        for sh in range(len(self._lanes)):
            # any lane record the _finish sweep above did not close
            # (stale occupant) dies with the cache it was writing into
            self._lane_drop(sh, "cache_lost")

    def _guarded_round(self, dispatch, budget) -> tuple:
        """Run one decode/verify round with fault recovery.

        ``dispatch(budget) -> (toks [n, S], counts [n], aux [n] | None)``
        performs the jitted round restricted to the slots whose budget row
        is nonzero (free slots always carry 0). The happy path is ONE
        retried call. On persistent failure, the round is ISOLATED: each
        occupied slot is re-dispatched alone (everyone else's budget masked
        to 0) with the SAME keys/tokens, which reproduces the group round's
        per-row results exactly — row b's logits see only slot b's cache,
        and the samplers draw per-row from the shared key — so surviving
        slots emit bit-identical tokens to a fault-free round. Slots that
        still fail alone are returned in ``failed`` (the caller retires
        them as ``"error"``). A failure that consumed the donated cache
        ends the round via ``_cache_lost``.

        Returns (toks, counts, aux, failed_slot_indices); counts rows of
        failed/finished slots are 0, so the step() walk skips them."""
        try:
            toks, counts, aux = retry(lambda: dispatch(budget),
                                      **self._retry)
            return toks, counts, aux, []
        except Exception as e:  # noqa: BLE001 - recovery, rethrown never
            _log_dispatch_failure("round", "active slots", e)
        n = len(self._slots)
        counts_out = np.zeros(n, np.int64)
        toks_out = aux_out = None
        failed: list = []
        if not self._cache_ok():
            self._cache_lost()
            return np.zeros((n, 1), np.int32), counts_out, None, []
        for i in range(n):
            if self._slots[i] is None or budget[i] <= 0:
                continue
            solo = np.zeros_like(budget)
            solo[i] = budget[i]
            try:
                t, c, a = retry(lambda: dispatch(solo), **self._retry)
            except Exception as e:  # noqa: BLE001 - isolated to slot i
                _log_dispatch_failure("solo", f"slot {i}", e)
                if not self._cache_ok():
                    # mid-isolation cache loss: everyone still parked fails
                    self._cache_lost()
                    return (np.zeros((n, 1), np.int32),
                            np.zeros(n, np.int64), None, [])
                failed.append(i)
                continue
            if toks_out is None:
                toks_out = np.zeros_like(t)
                aux_out = None if a is None else np.zeros_like(a)
            toks_out[i] = t[i]
            counts_out[i] = c[i]
            if a is not None:
                aux_out[i] = a[i]
        if toks_out is None:  # every occupied slot failed alone
            toks_out = np.zeros((n, 1), np.int32)
        return toks_out, counts_out, aux_out, failed

    def _draft(self, lens, kinds):
        """Propose draft tokens for every occupied slot — ``_spec_round``'s
        drafting stage, shared with the overlap issue path (where it runs
        INSIDE the device-busy window, from host state that is one round
        stale; a stale guess only costs acceptance, never correctness —
        the slot-keyed verify's sample-and-match emission is independent
        of the draft values). Returns the [slots, spec_len + 1] token
        block; column 0 is the host's last-token view (the overlap path
        overrides it with the device-carried row at dispatch)."""
        g = self.engine.spec_len
        n = len(self._slots)
        reg = self.obs.registry
        tokens = np.zeros((n, g + 1), np.int32)
        with self.obs.tracer.span("draft", spec_len=g):
            learned = [i for i, s in enumerate(self._slots)
                       if s is not None and kinds[i] == "learned"
                       and lens[i] > 0]
            batch = None
            if learned:
                ld = self._drafters["learned"]
                t0 = self._clock()
                batch = ld.propose_batch(self._last_tok, self._hidden, g)
                reg.counter("picotron_dispatch_total",
                            "engine dispatches by kind",
                            kind="draft").inc()
                self.engine.observe_dispatch("draft",
                                             self._clock() - t0)
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                tokens[i, 0] = self._last_tok[i]
                gi = int(lens[i])
                if gi == 0:
                    continue  # this slot rides the dispatch draft-free
                if kinds[i] == "learned":
                    tokens[i, 1: 1 + gi] = batch[i, :gi]
                    continue
                d = self._drafters.get(kinds[i], self.drafter)
                hist = np.asarray(list(s.req.prompt) + s.generated,
                                  np.int32)
                if getattr(d, "stateful", False):
                    tokens[i, 1: 1 + gi] = d.propose(hist, gi,
                                                     ctx=s.req.uid)
                else:
                    tokens[i, 1: 1 + gi] = d.propose(hist, gi)
        return tokens

    def _spec_account(self, lens, kinds, accepted, budget, failed) -> None:
        """Accumulate one verify round's acceptance stats: the lifetime
        totals, the per-slot and per-drafter registry counter families the
        controller and the bench read, and the controller's own record —
        shared by the synchronous round and the overlap sync stage."""
        reg = self.obs.registry
        for i, s in enumerate(self._slots):
            if s is None or i in failed or budget[i] <= 0:
                continue
            gi = int(lens[i])
            if gi == 0:
                continue
            acc = int(accepted[i]) if accepted is not None else 0
            self.draft_proposed += gi
            self._draft_proposed_total.inc(gi)
            self.draft_accepted += acc
            self._draft_accepted_total.inc(acc)
            # the labeled families the CONTROLLER reads back (telemetry
            # as a control surface) and the bench's per-drafter split
            reg.counter("picotron_slot_draft_proposed_total",
                        "draft tokens proposed, by slot",
                        slot=str(i)).inc(gi)
            reg.counter("picotron_slot_draft_accepted_total",
                        "draft tokens accepted, by slot",
                        slot=str(i)).inc(acc)
            kind = kinds[i] or "unknown"
            reg.counter("picotron_drafter_proposed_total",
                        "draft tokens proposed, by drafter",
                        drafter=kind).inc(gi)
            reg.counter("picotron_drafter_accepted_total",
                        "draft tokens accepted, by drafter",
                        drafter=kind).inc(acc)
            if self.controller is not None:
                self.controller.record(i, gi, acc)

    def _spec_round(self, budget, lens, kinds, lanes=None) -> tuple:
        """One draft-verify round: propose ``lens[i]`` tokens per occupied
        slot (per-slot RAGGED under the controller; the full
        ``engine.spec_len`` otherwise), dispatch ONE ``engine.verify``
        pass (fault-isolated like the decode round), and return its
        (emitted tokens, per-slot counts, failed slots).

        Drafting is per kind: "learned" slots draft TOGETHER in one small
        jitted dispatch from the device-resident hidden states
        (LearnedDrafter.propose_batch — timed into the "draft" latency
        histogram the controller's cost model reads); host drafters
        (n-gram, scripted) propose per slot from the slot's own history
        while the device is free. Acceptance stats accumulate here — the
        lifetime totals, the per-slot and per-drafter registry counter
        families the controller and the bench read, and the controller's
        obs-off shadow; the shared step() tail walks the emitted prefixes
        through ``_tokens_done`` exactly like a decode block's."""
        g = self.engine.spec_len
        t_round = self._clock()
        tokens = self._draft(lens, kinds)
        key = (self._base_keys if self._sched == "slot"
               else self._split())

        judged = _judged_as("verify", lanes is not None)

        def dispatch(b):
            self._phases.to("step/issue", judged)
            t0 = self._clock()
            self._note_issue(t0)
            res = self.engine.verify(
                self.params, self._cache, tokens, key, self._eos,
                b, self._temp, self._top_k, self._top_p, draft_len=lens,
                adapter_ids=(self._adapter if self.engine.adapters
                             is not None else None), lanes=lanes)
            # the lane's outputs wait for _lane_land; next_tok (the
            # overlap feed) is ignored here — see step()'s closure
            self._cache, self._lane_scratch = res.cache, res.lane
            self.decode_dispatches += 1
            self._phases.to("step/sync", judged)
            t_sync = self._clock()
            out = self._sync_outputs(res)
            self._merge_hidden(res.hidden, out[1])
            t1 = self._clock()
            self._phases.to("step/deliver")
            dt_sync = t1 - t_sync
            with self._scratch_mu:
                self._host_sync_s = dt_sync
            self._note_sync_end(t0, t1)
            self.engine.observe_dispatch("verify", t1 - t0)
            self.obs.tracer.record(
                "dispatch/verify", t0, t1,
                slots=int(np.count_nonzero(np.asarray(b) > 0)),
                draft_len=g, host_sync_s=round(dt_sync, 6))
            return out

        emitted, counts, accepted, failed = self._guarded_round(
            dispatch, budget)
        self._spec_account(lens, kinds, accepted, budget, failed)
        self._slot_spans(
            "verify", t_round, budget, counts, failed,
            extra=lambda i: {"draft_len": int(lens[i]),
                             "accepted": (int(accepted[i])
                                          if accepted is not None else 0)})
        return emitted, counts, failed
