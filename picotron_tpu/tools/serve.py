"""Serving front end: a resilient stdlib-only HTTP server over the batcher.

    python -m picotron_tpu.tools.serve --config exp.json \
        --load-path checkpoints --port 8000

The missing layer between ``ContinuousBatcher`` (a host-side scheduling
loop) and "serves heavy traffic": admission control, load shedding, health
surfaces, graceful drain, and a stall watchdog — the things that decide
whether one bad request or one sick dispatch takes down every other
request in flight (docs/SERVING.md). Stdlib only (``http.server``,
``threading``, ``json``): the front end must not be the component with the
exotic dependency.

API (all bodies JSON):

- ``POST /generate`` — ``{"prompt": [ids], "max_new_tokens", "temperature",
  "top_k", "top_p", "eos_id", "timeout_s", "stream", "uid",
  "request_id"}`` (all but ``prompt`` optional). Non-streaming: one JSON
  document with ``tokens`` and ``finish_reason``
  (``eos|length|timeout|shed|error``); HTTP status 200 for served
  outcomes, 503 + ``Retry-After`` when shed, 500 on ``error``.
  ``"stream": true``: NDJSON events ``{"event":"token",...}`` per
  generated token, then one ``{"event":"done", ...}`` carrying the full
  result. A client-supplied ``request_id`` (the router's correlation
  key) is echoed on every token row, the done row, and the non-streaming
  document, falling back to the server ``uid``.
- ``GET /healthz`` — liveness: 200 while the dispatch loop is making
  progress, 503 once the watchdog sees a stall (supervisors restart on
  this, exactly like ``tools/supervise.py``'s heartbeat rule).
- ``GET /readyz`` — readiness: 200 only when accepting work; the 503
  body carries ``"state": "draining" | "stalled" | "dead"`` so a poller
  (the multi-replica router, tools/router.py) can tell a GRACEFUL drain
  (stop placing, no breaker action) from a sick replica.
- ``GET /statz`` — the batcher's ``stats()`` (terminal-state counters,
  queue-wait / time-to-first-token percentiles) plus the server's
  admission-rejection counters and drain/stall state.
- ``GET /metrics`` — Prometheus text exposition of the engine/batcher/
  front-end registry plus the process-wide resilience counters
  (picotron_tpu/obs, docs/OBSERVABILITY.md). The counters are the SAME
  instruments ``/statz`` reads, so the two surfaces cannot disagree.
  Speculative engines additionally export ``picotron_spec_accept_rate``
  and ``picotron_spec_len`` gauges, refreshed on render exactly like the
  queue-depth gauges (batcher.refresh_gauges) — the fabric's router can
  see each replica's live speculation health off the scrape, and
  ``/statz`` mirrors them as ``accept_rate`` / ``spec_len_effective``
  (plus the controller's decision counts when
  ``inference.spec_controller`` is on).
- ``GET /tracez`` — the process span ring as Chrome-trace JSON: each
  request's queue-wait -> prefill -> per-dispatch -> delivery chain,
  parented, and the spans of the rounds the stall judge found slow
  (docs/OBSERVABILITY.md "Stalls"; ``/statz`` ``stalls`` has their
  records). Validate/query with ``tools/trace_dump.py``.
- ``POST /profilez`` — start one timed ``jax.profiler`` capture
  (``{"seconds", "dir"}`` optional; defaults from ``obs.profile_dir`` /
  ``obs.profile_seconds``); 409 while one is running. The CLI wires
  SIGUSR2 to the same capture.
- ``GET /tenants`` / ``POST /tenants`` / ``DELETE /tenants/<name>`` —
  the multi-tenant admin plane (inference/tenancy.py, docs/SERVING.md
  "Multi-tenant serving"): list registered tenants + adapter-pack
  occupancy, hot-add one tenant (its LoRA weights land in a free pack
  slot — no recompile), hot-remove (the slot zeroes back to null).
  ``/generate``'s optional ``"tenant"`` field selects the serving
  identity; unknown names are a 400, never a silent base fallback.
  Per-tenant quotas 429 with ``"budget": "tenant_tokens" |
  "tenant_pages"`` in the body; global budget 429s carry ``"tokens"`` /
  ``"pages"`` — a client backoff can tell its own quota from fleet
  pressure. Only present when a registry is configured
  (``inference.tenancy`` or ``--tenant-manifest``).
- ``POST /kv/export`` / ``GET|POST /kv/pages`` / ``POST /kv/import`` —
  the prefill/decode disaggregation plane (``inference.role``,
  inference/page_transport.py, docs/SERVING.md "Disaggregated
  prefill/decode"): a prefill worker runs admission + prefill and hands
  the finished KV pool pages off as a byte-exact payload (+ the first
  sampled token); ``/kv/pages`` looks up the longest radix-cached
  prefix; ``/kv/import`` lands a payload in the local radix cache; and
  ``/generate``'s ``"kv"`` field seats a full-prompt payload with zero
  prefill dispatches. Paged layout only.

Admission control (checked atomically at POST time):

- **bounded wait queue** — more than ``--max-queue`` waiting requests
  (default: 64, or twice the slots where that is more) is a
  503 (the queue is where latency hides; past the bound, waiting is worse
  for the client than retrying another replica);
- **token budget** — the worst-case token commitment (prompt +
  window-capped ``max_new_tokens``) of every live request is capped by
  ``--token-budget`` (default: ``slots * max_seq_len``, the cache's real
  capacity); past it new work is a 429. Both carry ``Retry-After``.
- **page budget** (``inference.kv_layout: "paged"`` only) — requests are
  additionally priced in KV POOL PAGES (``ceil(commitment / page_len)``,
  not a contiguous worst-case strip) against the pool size; past it, 429
  with a ``Retry-After`` scaled to the page deficit. ``/statz`` then also
  carries the pool occupancy and prefix-cache hit stats
  (``kv_pages_*``, ``prefix_hit_rate``, ``cow_copies`` — from
  ``batcher.stats()``; docs/SERVING.md).

Graceful drain (the ``resilience.preemption.PreemptionGuard`` pattern):
SIGTERM/SIGINT flips readiness, sheds the queued-but-unstarted requests
(``finish_reason "shed"``), lets in-flight slots run to completion, then
exits 0. A second signal aborts immediately (the operator means it).

``--smoke`` is the ``make serve-smoke`` target: tiny CPU model, ephemeral
port, one scripted client (health checks, a POST, a streamed POST, SIGTERM
drain with accounting) — exits nonzero on any malfunction.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import queue
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional


class AdmissionError(Exception):
    """A request rejected at the door (shed before submission).
    ``extra`` rides into the JSON error body — budget rejections use it
    to name WHICH budget tripped (``"budget": "tokens" | "pages" |
    "tenant_tokens" | "tenant_pages"``) so a router or client backoff
    can tell global pressure from its own quota."""

    def __init__(self, status: int, reason: str, retry_after: int = 1,
                 **extra):
        super().__init__(reason)
        self.status = status
        self.reason = reason
        self.retry_after = retry_after
        self.extra = extra


class _Waiter:
    """Per-request rendezvous between the dispatch loop and its HTTP
    handler thread: token events stream through the queue, the final
    GenerationResult ends it."""

    def __init__(self):
        # put and get in C, no Condition in Python: an event costs both
        # threads less of the GIL they share with the dispatch loop
        self.events: queue.SimpleQueue = queue.SimpleQueue()

    def put_tokens(self, toks: list) -> None:
        self.events.put(("tokens", toks))

    def put_done(self, result) -> None:
        self.events.put(("done", result))


class FrontEnd:
    """Owns the batcher, the dispatch loop thread, and the watchdog.

    All batcher access is serialized by ``_mu`` (the batcher is not
    thread-safe); HTTP handler threads only touch it for the short
    admission check + submit, the dispatch loop for step()/result
    draining. ``guard`` is a ``PreemptionGuard`` (not installed here —
    the CLI installs it on the main thread; tests drive ``begin_drain``
    directly)."""

    # submit() asks for the batcher lock this long at a time, and looks at
    # the watchdog's verdict between asks
    SUBMIT_WAIT_SLICE_S = 10.0

    def __init__(self, engine, params, *, seed: int = 0,
                 max_queue: Optional[int] = None,
                 token_budget: Optional[int] = None,
                 default_timeout_s: Optional[float] = None,
                 stall_timeout_s: float = 60.0,
                 watchdog_poll_s: float = 0.25,
                 tenants=None, log=print):
        from picotron_tpu.inference import ContinuousBatcher
        from picotron_tpu.obs import ProfileCapture
        from picotron_tpu.resilience.preemption import PreemptionGuard

        self.engine = engine
        self.obs = engine.obs  # one registry across engine/batcher/front end
        ocfg = engine.cfg.obs
        self.profiler = ProfileCapture(
            ocfg.profile_dir, ocfg.profile_seconds,
            log=lambda m: self._event("profiler", note=m),
            tracer=self.obs.tracer)
        # two batches of waiting requests at the least (64 up to 32 slots):
        # a closed loop's clients all arrive at once, and at 64 slots a
        # bound of 64 shed the ones behind the first batch
        self.max_queue = int(max_queue if max_queue is not None
                             else max(64, 2 * engine.slots))
        self.token_budget = int(token_budget if token_budget is not None
                                else engine.slots * engine.max_seq_len)
        self.default_timeout_s = default_timeout_s
        self.stall_timeout_s = float(stall_timeout_s)
        self.watchdog_poll_s = float(watchdog_poll_s)
        self.guard = PreemptionGuard()
        self._log = log
        self._mu = threading.Lock()
        self._uid_mu = threading.Lock()  # uid counter only: never wait on
        # _mu before the bounded acquire below, or a wedged dispatch parks
        # every uid-less submission forever instead of shedding it after 10s
        self._wake = threading.Event()
        self._waiters: dict = {}
        self._batcher = ContinuousBatcher(engine, params, seed=seed,
                                          on_tokens=self._on_tokens)
        # model-memory gauge: the router's /metrics scrape (tools/
        # router.py) can see per-replica resident weight bytes — int8
        # values + per-channel scales included, so a quantized replica
        # reports ~half its bf16 twin (docs/INFERENCE.md "Quantized
        # weights"); set once: weights never change size mid-serve
        self.weight_bytes = engine.model.param_bytes(params)
        self.obs.registry.gauge(
            "picotron_weight_bytes",
            "model weight bytes resident on this replica").set(
                float(self.weight_bytes))
        # disaggregated serving role (inference.role, docs/SERVING.md
        # "Disaggregated prefill/decode"): "both" serves exactly as
        # before; "prefill" runs admission + prefill only and hands KV
        # pages off via POST /kv/export (its /generate sheds); "decode"
        # seats imported pages and runs the decode/spec loop. The role
        # gauge lets a router scrape tell a prefill worker from an idle
        # decode target (it would otherwise score as one).
        self.role = engine.cfg.inference.role
        self.obs.registry.gauge(
            "picotron_serve_role",
            "serving role of this replica", role=self.role).set(1.0)
        # multi-tenant registry (inference/tenancy.py, docs/SERVING.md
        # "Multi-tenant serving"): None = single-tenant serving, every
        # request anonymous base traffic, exactly as before. When set,
        # requests may name a tenant ("tenant" field) — UNKNOWN names
        # are a 400, never a silent base fallback (a typo'd tenant must
        # not dodge its quota) — and admission becomes priority-aware:
        # under budget pressure queued lower classes shed before a
        # higher-class arrival 429s.
        self.tenants = tenants
        self.draining = False
        # leaf lock for the drain flag: POST /drain handler threads, the
        # dispatch loop's guard check, and drain_and_join all race on it;
        # drain_begins counts WINNING initiations (the regression surface
        # for a double-run of the drain machinery — it must stay 1 when
        # SIGTERM lands during an HTTP-initiated drain)
        self._drain_mu = threading.Lock()
        self.drain_begins = 0
        self.stopped = threading.Event()  # dispatch loop has exited
        self.dead = False  # loop died on an exception (vs clean drain)
        self.stalled = False
        self.stalls = 0  # stall episodes the watchdog flagged
        # a CounterDict: plain-dict reads (tests, /statz) with every
        # write mirrored into picotron_rejections_total{reason} — the
        # /metrics rendering of the same numbers
        self.rejections = self.obs.registry.counter_dict(
            "picotron_rejections_total",
            ("queue_full", "token_budget", "page_budget", "tenant_quota",
             "draining", "stalled", "dead", "role"),
            help="admission sheds by reason", label="reason")
        # leaf lock for the rejection counters: the "stalled" increment
        # happens precisely when _mu could NOT be acquired, so the
        # counters need their own guard (picolint PICO-C003 — concurrent
        # timed-out handlers would lose increments). Always taken last
        # (inside _mu where both are held), never while waiting on _mu.
        self._rej_mu = threading.Lock()
        # what a client waits in submit() before the batcher sees its
        # request: the dispatch loop holds _mu for a whole step
        self._submit_wait_hist = self.obs.registry.histogram(
            "picotron_submit_lock_wait_seconds",
            "handler thread's wait for the batcher lock in submit()")
        # the loop's own two phases join the batcher's five under the stall
        # judge (loop/idle does not: waiting for work is no stall), and the
        # watchdog's sleeps say whether the process was alive meanwhile
        self.obs.stalls.register("loop/lock_wait", "loop/results")
        self.obs.stalls.register_watchdog()
        self._uid_seq = 0
        self._start_t = time.monotonic()
        self._progress_t = time.monotonic()
        self._req_t: dict = {}  # uid -> wall submit time (request log)
        self._threads: list = []
        self._on_drained = None  # callback once drain completes (CLI: shutdown)

    # ---- lifecycle --------------------------------------------------------

    def start(self) -> None:
        for name, fn in (("serve-dispatch", self._loop),
                         ("serve-watchdog", self._watchdog)):
            t = threading.Thread(target=fn, name=name, daemon=True)
            t.start()
            self._threads.append(t)

    def begin_drain(self) -> bool:
        """Stop admitting, shed the unstarted queue, finish in-flight
        slots, then stop the dispatch loop (readiness goes 503 at once).
        Idempotent AND race-free: POST /drain (a fleet controller's
        scale-down) and the PreemptionGuard's SIGTERM path can land
        concurrently — exactly one caller wins the flag under the leaf
        lock, so the drain machinery (the event, the shed, the eventual
        exit) runs once no matter how many initiators fire. Returns
        whether THIS caller won the initiation."""
        with self._drain_mu:
            first = not self.draining
            self.draining = True
            if first:
                self.drain_begins += 1
        if first:
            self._event("drain_begin")
        self._wake.set()
        return first

    def drain(self) -> dict:
        """POST /drain: the fleet controller's scale-down surface —
        start a graceful drain over HTTP (readyz flips to "draining" at
        once, in-flight finishes, the process exits 0 exactly as a
        SIGTERM drain would). 409 when there is nothing to start: the
        loop already exited (dead OR drained — no second drain can run)
        or a drain already owns the flag (the first initiator holds the
        contract; a controller seeing 409 treats the drain as already
        under way)."""
        if self.dead or self.stopped.is_set():
            raise AdmissionError(
                409, "dispatch loop already exited", retry_after=0,
                state="dead" if self.dead else "stopped")
        if not self.begin_drain():
            raise AdmissionError(409, "already draining", retry_after=0,
                                 state="draining")
        return {"ok": True, "state": "draining"}

    def join(self, timeout: Optional[float] = None) -> None:
        self.stopped.wait(timeout)

    # ---- admission --------------------------------------------------------

    def submit(self, spec: dict, _internal: bool = False) -> tuple:
        """Admission-check + submit one request. Returns (uid, waiter) or
        raises AdmissionError (the caller turns it into 429/503).
        ``_internal`` marks the /kv/export path's own 1-token submission,
        which a role=prefill replica must accept even though its public
        /generate sheds."""
        from picotron_tpu.inference import Request

        if self.role == "prefill" and not _internal:
            # a prefill worker's dispatch rounds belong to prefills; a
            # decode stream here would be the interference the role split
            # exists to remove. 503 (not 400): the client did nothing
            # wrong, the router just mis-placed.
            self._reject("role")
            raise AdmissionError(
                503, "replica serves prefill only (inference.role: "
                     "prefill); use POST /kv/export", retry_after=5)
        prompt = spec.get("prompt")
        if not isinstance(prompt, list) or not prompt \
                or not all(isinstance(t, int) for t in prompt):
            raise AdmissionError(400, "prompt must be a non-empty list of "
                                      "token ids", retry_after=0)
        kv = spec.get("kv")
        if kv is not None:
            # the disaggregated handoff payload: validate its spec BEFORE
            # taking a slot. A payload this replica can never consume —
            # contiguous layout, mismatched page geometry/dtype — is
            # DROPPED, not rejected: the request is still perfectly
            # servable by self-prefilling, and a mixed or mid-upgrade
            # fleet must degrade to colocated behavior, never to client
            # 400s (the capability gap is logged + counted).
            from picotron_tpu.inference import page_transport

            if not isinstance(kv, dict):
                raise AdmissionError(400, "kv must be a transport payload "
                                          "object", retry_after=0)
            why = None
            if self.engine.paged is None:
                why = "contiguous kv_layout (no page pool)"
            else:
                try:
                    page_transport.check_spec(self.engine, kv)
                except page_transport.TransportError as e:
                    why = str(e)
            if why is not None:
                self.obs.registry.counter(
                    "picotron_handoff_dropped_total",
                    "kv payloads dropped as locally unusable").inc()
                self._event("kv_dropped", why=why[:200])
                kv = None
        tenant, slot = self._resolve_tenant(spec.get("tenant"))
        timeout_s = spec.get("timeout_s", self.default_timeout_s)
        try:
            req = Request(
                uid=str(spec.get("uid") or self._next_uid()),
                prompt=list(prompt),
                max_new_tokens=int(spec.get("max_new_tokens", 32)),
                temperature=float(spec.get("temperature", 0.0)),
                top_k=int(spec.get("top_k", 0)),
                top_p=float(spec.get("top_p", 1.0)),
                eos_id=spec.get("eos_id"),
                timeout_s=None if timeout_s is None else float(timeout_s),
                kv_import=kv,
                tenant=self._tenant_salt(tenant),
                priority=tenant.priority if tenant is not None else 1,
                adapter_slot=slot,
                ttft_slo_ms=(tenant.ttft_slo_ms if tenant is not None
                             else None),
                tpot_slo_ms=(tenant.tpot_slo_ms if tenant is not None
                             else None))
        except (TypeError, ValueError) as e:
            raise AdmissionError(400, f"bad request field: {e}",
                                 retry_after=0)
        if req.max_new_tokens < 1:
            # a zero-budget request would hold a slot forever (no token ever
            # completes it); a negative one corrupts the token-budget math
            raise AdmissionError(400, "max_new_tokens must be >= 1",
                                 retry_after=0)
        # window-capped commitment (the same pricing token_load() uses): a
        # budget beyond max_seq_len can never be generated, so counting it
        # raw would 429 a servable request forever. Reads only the engine's
        # window — safe before taking _mu.
        cost = self._batcher.commitment(req)
        # bounded wait for the batcher lock: during a wedged dispatch (the
        # stall the watchdog flags) admission SHEDS instead of parking
        # handler threads on the lock forever. The wait is the request
        # chain's first link (a shed one keeps its span, with the error).
        with self.obs.timed("submit/lock_wait", self._submit_wait_hist,
                            uid=req.uid) as waited:
            # a long step (several long prompts prefilled one after the
            # other) is not a wedged one, and the watchdog is the one judge
            # of that: wait in slices, and shed at the end of the first
            # slice that finds the loop called stalled (or, should no
            # watchdog be running, once this wait is itself as long as a
            # step may take; with the watchdog off that is the first slice)
            t0 = time.monotonic()
            while not self._mu.acquire(timeout=self.SUBMIT_WAIT_SLICE_S):
                if self.stalled or \
                        time.monotonic() - t0 >= self.stall_timeout_s:
                    self._reject("stalled")
                    raise AdmissionError(
                        503, "dispatch stalled (admission unavailable)",
                        retry_after=10)
        try:
            if self.stopped.is_set():
                # the dispatch loop is gone (drain done, or it died on an
                # unexpected exception): nothing will ever serve this
                # request — shed it instead of stranding the handler on a
                # waiter no loop will complete
                self._reject("dead")
                raise AdmissionError(
                    503, "dispatch loop exited (restart required)",
                    retry_after=30)
            if self.draining:
                self._reject("draining")
                raise AdmissionError(
                    503, "draining (restart in progress)", retry_after=5)
            if self._batcher.queue_depth >= self.max_queue:
                # the wait queue is bounded: past it, queueing only grows
                # the client's latency — shed instead
                self._reject("queue_full")
                raise AdmissionError(
                    503, f"wait queue full ({self.max_queue})",
                    retry_after=max(1, self.max_queue // 8))
            # per-tenant quotas FIRST: a tenant over its own ceiling is
            # ITS problem — it never triggers lower-class shedding, and
            # the 429 body names the tenant budget so its backoff does
            # not read as global pressure (Retry-After scales to the
            # tenant's own deficit, the PR 7 page-deficit pattern).
            if tenant is not None and tenant.max_tokens is not None:
                tload = self._batcher.tenant_token_load(req.tenant)
                if tload + cost > tenant.max_tokens:
                    deficit = tload + cost - tenant.max_tokens
                    self._reject("tenant_quota")
                    raise AdmissionError(
                        429,
                        f"tenant {tenant.name!r} token quota exhausted "
                        f"({tenant.max_tokens})",
                        retry_after=min(30, 1 + deficit
                                        // max(1, tenant.max_tokens // 4)),
                        budget="tenant_tokens", tenant=tenant.name)
            if (tenant is not None and tenant.max_pages is not None
                    and self.engine.paged is not None):
                pneed = self._batcher.page_commitment(req)
                pload = self._batcher.tenant_page_load(req.tenant)
                if pload + pneed > tenant.max_pages:
                    deficit = pload + pneed - tenant.max_pages
                    self._reject("tenant_quota")
                    raise AdmissionError(
                        429,
                        f"tenant {tenant.name!r} page quota exhausted "
                        f"({tenant.max_pages})",
                        retry_after=min(30, 1 + deficit
                                        // max(1, tenant.max_pages // 4)),
                        budget="tenant_pages", tenant=tenant.name)
            if self._batcher.token_load() + cost > self.token_budget:
                # before 429ing, a positive-class arrival sheds QUEUED
                # strictly-lower-class work (lowest class first) until
                # its commitment fits — priority is meaningless if a
                # full budget holds classes equal
                deficit = (self._batcher.token_load() + cost
                           - self.token_budget)
                if req.priority > 0:
                    self._batcher.shed_lower_priority(req.priority,
                                                      tokens=deficit)
                if self._batcher.token_load() + cost > self.token_budget:
                    self._reject("token_budget")
                    raise AdmissionError(
                        429, f"token budget exhausted ({self.token_budget})",
                        retry_after=1, budget="tokens")
            if self.engine.paged is not None:
                # paged layout: price in POOL PAGES, not contiguous
                # strips — ceil(commitment / page_len) against the pool,
                # with Retry-After scaled to the page deficit (deeper
                # overload -> back off longer; capped at 30s)
                need = self._batcher.page_commitment(req)
                usable = self.engine.paged.usable_pages
                load = self._batcher.page_load()
                if load + need > usable and req.priority > 0:
                    self._batcher.shed_lower_priority(
                        req.priority, pages=load + need - usable)
                    load = self._batcher.page_load()
                if load + need > usable:
                    deficit = load + need - usable
                    self._reject("page_budget")
                    raise AdmissionError(
                        429,
                        f"kv page pool exhausted (need {need} of "
                        f"{usable - min(load, usable)} pages free)",
                        retry_after=min(30, 1 + deficit
                                        // max(1, usable // 4)),
                        budget="pages")
            if req.uid in self._waiters:
                raise AdmissionError(400, f"duplicate uid {req.uid!r}",
                                     retry_after=0)
            waiter = _Waiter()
            self._waiters[req.uid] = waiter
            self._req_t[req.uid] = time.monotonic()
            try:
                # validates prompt vs max_seq_len
                self._batcher.submit(req, waited=waited)
            except ValueError as e:
                self._waiters.pop(req.uid, None)
                self._req_t.pop(req.uid, None)
                raise AdmissionError(400, str(e), retry_after=0)
        finally:
            self._mu.release()
        self._wake.set()
        return req.uid, waiter

    def _reject(self, key: str) -> None:
        """Count one shed under the counters' own leaf lock — reachable
        both with and without ``_mu`` held (the "stalled" path fires
        exactly because ``_mu`` was unavailable)."""
        with self._rej_mu:
            self.rejections[key] += 1

    def _next_uid(self) -> str:
        with self._uid_mu:
            self._uid_seq += 1
            return f"r{self._uid_seq}"

    # ---- multi-tenant serving (inference/tenancy.py) -----------------------

    def _resolve_tenant(self, name) -> tuple:
        """(Tenant, adapter slot) for a request's ``tenant`` field, or
        (None, 0) for anonymous traffic on a single-tenant server.
        Unknown names are a 400 — never a silent base fallback."""
        if name is not None and not isinstance(name, str):
            raise AdmissionError(400, "tenant must be a string",
                                 retry_after=0)
        if self.tenants is None:
            if name:
                raise AdmissionError(
                    400, f"no tenant registry configured (got tenant "
                         f"{name!r}; set inference.tenancy or "
                         f"--tenant-manifest)", retry_after=0)
            return None, 0
        try:
            return self.tenants.resolve(name)
        except KeyError:
            raise AdmissionError(
                400, f"unknown tenant {name!r} (register via POST "
                     f"/tenants)", retry_after=0)

    @staticmethod
    def _tenant_salt(tenant) -> str:
        """The cache-isolation key a tenant stamps on radix subtrees and
        transport chunks. The base identity salts as "" — anonymous
        traffic keeps sharing the pre-tenancy default domain."""
        from picotron_tpu.inference.tenancy import BASE_TENANT

        if tenant is None or tenant.name == BASE_TENANT:
            return ""
        return tenant.name

    def tenants_snapshot(self) -> dict:
        """GET /tenants: every registered tenant + pack occupancy."""
        if self.tenants is None:
            raise AdmissionError(400, "no tenant registry configured",
                                 retry_after=0)
        out = {"tenants": self.tenants.snapshot()}
        pack = self.tenants.pack
        if pack is not None:
            out["pack"] = {"slots": pack.slots, "rank": pack.rank,
                           "version": pack.version,
                           "adapter_bytes_per_token":
                               pack.bytes_per_token()}
        return out

    def tenants_add(self, spec: dict) -> dict:
        """POST /tenants: hot-register one tenant (adapter weights land
        in a free pack slot; the next dispatch re-places the pack — no
        recompile, shapes are capacity-static)."""
        from picotron_tpu.inference.tenancy import Tenant

        if self.tenants is None:
            raise AdmissionError(
                400, "no tenant registry configured (start with "
                     "inference.tenancy or --tenant-manifest)",
                retry_after=0)
        try:
            tenant = Tenant.from_dict(spec)
            slot = self.tenants.add(tenant)
        except (TypeError, ValueError) as e:
            # duplicate names and a full pack are conflicts with current
            # state (retryable after a remove), not malformed requests —
            # but Tenant.from_dict's shape errors are; 409 covers both
            # without parsing messages, and the body says which
            raise AdmissionError(409, str(e), retry_after=0)
        self._event("tenant_add", tenant=tenant.name, slot=slot,
                    priority=tenant.priority, rank=tenant.adapter_rank)
        return {"ok": True, "tenant": tenant.name, "adapter_slot": slot}

    def tenants_remove(self, name: str) -> dict:
        """DELETE /tenants/<name>: hot-deregister. The slot zeroes back
        to null, so in-flight rows degrade to base output — never to
        another tenant's adapter."""
        if self.tenants is None:
            raise AdmissionError(400, "no tenant registry configured",
                                 retry_after=0)
        try:
            self.tenants.remove(name)
        except KeyError:
            raise AdmissionError(404, f"no tenant {name!r}",
                                 retry_after=0)
        self._event("tenant_remove", tenant=name)
        return {"ok": True, "tenant": name}

    # ---- KV-page transport (prefill/decode disaggregation) ----------------

    def _require_paged(self) -> None:
        if self.engine.paged is None:
            raise AdmissionError(
                503, "kv transport requires inference.kv_layout: 'paged' "
                     "on this replica", retry_after=0)

    def kv_export(self, spec: dict) -> dict:
        """POST /kv/export: run ``spec``'s prompt through the normal
        admission + prefill path with a 1-token budget (the one sampled
        token IS the handoff's seat state), then serialize the prompt's
        radix-cached pages as a transport payload. A repeat of a cached
        prompt prefills only its final token — the radix cache makes the
        prefill worker the cluster's prefix bank. Raises AdmissionError
        on shed/failure (the router's fallback trigger)."""
        if self.role == "decode":
            raise AdmissionError(
                503, "replica serves decode only (inference.role: "
                     "decode); export from a prefill/both replica",
                retry_after=5)
        self._require_paged()
        prompt = spec.get("prompt")
        # the tenant salts the exported chunk keys exactly as it salts
        # the radix domain the prefill lands in (resolved/validated again
        # inside submit; this call only needs the canonical salt)
        salt = self._tenant_salt(self._resolve_tenant(
            spec.get("tenant"))[0])
        sub = dict(spec)
        sub["max_new_tokens"] = 1
        sub.pop("stream", None)
        sub.pop("kv", None)
        uid, waiter = self.submit(sub, _internal=True)
        while True:
            kind, val = waiter.events.get()
            if kind == "done":
                res = val
                break
        if res.finish_reason == "shed":
            raise AdmissionError(503, "prefill shed (draining)",
                                 retry_after=5)
        if res.finish_reason not in ("length", "eos") or not res.tokens:
            raise AdmissionError(
                500, f"prefill finished {res.finish_reason!r}",
                retry_after=1)
        first = int(res.tokens[0])
        if not self._mu.acquire(timeout=30.0):
            raise AdmissionError(503, "dispatch stalled (export "
                                      "unavailable)", retry_after=10)
        try:
            payload = self._batcher.export_prefix(prompt,
                                                  first_token=first,
                                                  tenant=salt)
        finally:
            self._mu.release()
        self._event("kv_export", uid=uid, tokens=len(payload["token_ids"]),
                    pages=len(payload["pages"]),
                    bytes=payload["bytes_total"],
                    ttft_s=_r(res.ttft_s))
        return {"uid": uid, "kv": payload,
                "queue_wait_s": _r(res.queue_wait_s),
                "ttft_s": _r(res.ttft_s)}

    def kv_import(self, payload: dict) -> dict:
        """POST /kv/import: land a transport payload in the local pool +
        radix cache (no slot — the cross-replica prefix-cache transfer).
        A subsequent /generate for a prompt extending it radix-hits
        locally, zero prefill dispatches for the covered prefix."""
        from picotron_tpu.inference import page_transport
        from picotron_tpu.inference.paged_kv import PagePoolExhausted

        self._require_paged()
        if not self._mu.acquire(timeout=10.0):
            raise AdmissionError(503, "dispatch stalled (import "
                                      "unavailable)", retry_after=10)
        try:
            if self.stopped.is_set() or self.draining:
                raise AdmissionError(503, "draining (restart in progress)",
                                     retry_after=5)
            try:
                info = self._batcher.import_prefix(payload)
            except page_transport.TransportError as e:
                raise AdmissionError(400, f"bad kv payload: {e}",
                                     retry_after=0)
            except PagePoolExhausted:
                raise AdmissionError(429, "kv page pool exhausted",
                                     retry_after=5)
        finally:
            self._mu.release()
        self._event("kv_import", **info)
        return info

    def kv_pages(self, ids, tenant=None) -> dict:
        """GET/POST /kv/pages: the cross-replica prefix LOOKUP — the
        longest radix-cached prefix of ``ids`` as a transport payload
        (no first token: a lookup vouches for pages, not logits).
        ``matched`` 0 = miss (an empty payload, nothing to import).
        ``tenant`` scopes the lookup to that tenant's radix domain — a
        lookup must never vouch pages across the isolation boundary."""
        self._require_paged()
        salt = self._tenant_salt(self._resolve_tenant(tenant)[0])
        if (not isinstance(ids, list) or not ids
                or not all(isinstance(t, int) for t in ids)):
            raise AdmissionError(400, "ids must be a non-empty list of "
                                      "token ids", retry_after=0)
        if not self._mu.acquire(timeout=10.0):
            raise AdmissionError(503, "dispatch stalled (lookup "
                                      "unavailable)", retry_after=10)
        try:
            payload = self._batcher.export_prefix(ids, tenant=salt)
        finally:
            self._mu.release()
        return {"matched": len(payload["token_ids"]), "kv": payload}

    def kv_prefixes(self, limit: int = 4) -> dict:
        """GET /kv/prefixes: enumerate this replica's hottest radix-cached
        prefixes (token ids + owning tenant), hottest first — the surface
        a fleet controller's drain-time cache handoff walks (each entry
        round-trips /kv/pages here -> /kv/import at a survivor, so a
        drained worker's cache is not lost to the cluster). Bounded lock
        acquire like every scrape-plane surface: a wedged dispatch makes
        this degrade to 503, never deadlock."""
        self._require_paged()
        if limit < 1:
            raise AdmissionError(400, f"limit must be >= 1, got {limit}",
                                 retry_after=0)
        if not self._mu.acquire(timeout=10.0):
            raise AdmissionError(503, "dispatch stalled (enumeration "
                                      "unavailable)", retry_after=10)
        try:
            entries = self.engine.paged.radix.cached_prefixes(limit)
        finally:
            self._mu.release()
        return {"prefixes": [{"ids": list(ids), "tenant": salt or None}
                             for salt, ids in entries]}

    # ---- dispatch loop ----------------------------------------------------

    def _on_tokens(self, uid: str, toks: list) -> None:
        # called from inside batcher.step() (under _mu), once a slot and
        # round: one event, so one wake-up of the request's handler thread
        w = self._waiters.get(uid)
        if w is not None:
            w.put_tokens(toks)

    def _loop(self) -> None:
        phase = self.obs.phase
        # this thread's scoped spans are the loop's phases ("pt:" in a
        # profiler capture; handler threads' are "pt.req:")
        self.obs.tracer.claim_loop_thread()
        try:
            while True:
                if self.guard.triggered and not self.draining:
                    self.begin_drain()
                with contextlib.ExitStack() as results_phase:
                    with phase("loop/lock_wait"):
                        self._mu.acquire()
                    try:
                        if self.draining:
                            self._batcher.shed_pending()
                        if self._batcher.busy:
                            self._batcher.step()
                        # take_results .. the round's last _deliver
                        results_phase.enter_context(phase("loop/results"))
                        results = self._batcher.take_results()
                        busy = self._batcher.busy
                    finally:
                        self._mu.release()
                    self._progress_t = time.monotonic()
                    for uid, res in results.items():
                        self._deliver(uid, res)
                # a slow interval speaks when it happened, not at the
                # watchdog's 60 s; outside the lock, as every log line
                for rec in self.obs.stalls.take_slow():
                    self._event("slow_interval", **rec)
                if self.draining and not busy:
                    self._event("drain_done")
                    return
                if not busy:
                    self._idle()
        except BaseException as e:  # noqa: BLE001 - loop death is fatal news
            self._event("dispatch_loop_died",
                        error=f"{type(e).__name__}: {e}")
            # a dedicated latch, not `stalled`: the watchdog CLEARS stalled
            # on its next tick (progress looked recent), which would flip
            # healthz back to 200 on a dead server forever
            self.dead = True  # healthz goes 503: supervisors restart us
            raise
        finally:
            # never strand a blocked handler: whatever the loop's fate,
            # every still-registered waiter gets a terminal "error" result.
            # Under _mu, stopped BEFORE the snapshot: submit() checks
            # stopped under the same lock, so every admission either saw
            # it (shed 503) or registered its waiter before the snapshot
            # (delivered here) — no in-between request is stranded
            from picotron_tpu.inference.batcher import GenerationResult

            with self._mu:
                self.stopped.set()
                stranded = list(self._waiters)
            for uid in stranded:
                self._deliver(uid, GenerationResult(uid, [], [], "error"))
            self.obs.tracer.release_loop_thread()
            if self._on_drained is not None:
                self._on_drained()

    def _idle(self) -> None:
        """Nothing is busy: wait for a submission, a drain or the guard,
        in 50 ms slices. One ``loop/idle`` span and observation for the
        whole stretch, and no lock taken in it: an idle server must not
        push its last requests' chains out of the span ring."""
        with self.obs.phase("loop/idle"):
            while not (self._wake.wait(0.05) or self.draining
                       or self.guard.triggered or self._batcher.busy):
                self._progress_t = time.monotonic()
            self._wake.clear()

    def _deliver(self, uid: str, res) -> None:
        # the pops happen under _mu: handler threads INSERT these entries
        # under the same lock in submit(), and the duplicate-uid check
        # reads _waiters there — an unlocked pop here races both (picolint
        # PICO-C003). The log line and the waiter hand-off (a Queue put)
        # stay outside: neither needs the lock, and the log is file I/O
        # that must not stall admission (PICO-C002).
        with self._mu:
            t0 = self._req_t.pop(uid, None)
            w = self._waiters.pop(uid, None)
        self._event(
            "request", uid=uid, finish_reason=res.finish_reason,
            prompt_tokens=len(res.prompt), new_tokens=len(res.tokens),
            queue_wait_s=_r(res.queue_wait_s), ttft_s=_r(res.ttft_s),
            total_s=_r(None if t0 is None else time.monotonic() - t0))
        td = time.monotonic()
        if w is not None:
            w.put_done(res)
        # the chain's last link: hand-off to the waiting handler thread,
        # parented onto the request's (already-ended) root span
        if getattr(res, "span_id", None):
            self.obs.tracer.record("delivery", td, time.monotonic(),
                                   parent=res.span_id, uid=uid,
                                   finish_reason=res.finish_reason)

    def _watchdog(self) -> None:
        """Dispatch-stall detector, the in-process mirror of
        tools/supervise.py: while work exists, the loop must keep
        finishing steps; a silent gap longer than the threshold flips
        ``stalled`` (healthz 503 — the supervisor's restart signal).
        Recovery (the next completed step) clears it."""
        if self.stall_timeout_s <= 0:
            return
        while not self.stopped.is_set():
            self._nap()
            busy = self._batcher.busy  # racy read: a threshold, not a ledger
            age = time.monotonic() - self._progress_t
            if busy and age > self.stall_timeout_s:
                if not self.stalled:
                    self.stalled = True
                    self.stalls += 1
                    self._event("stall", age_s=_r(age),
                                threshold_s=self.stall_timeout_s)
            elif self.stalled:
                self.stalled = False
                self._event("stall_recovered")

    def _nap(self, sleep=time.sleep, clock=time.monotonic) -> None:
        """One sleep of the watchdog, and how far it overran counted
        (``picotron_watchdog_oversleep_seconds_total``). This thread does
        none of the loop's work and holds none of its locks, so a sleep
        that overran by seconds is the whole process standing still, not
        the loop waiting for a device program."""
        t0 = clock()
        sleep(self.watchdog_poll_s)
        self.obs.stalls.oversleep(clock() - t0 - self.watchdog_poll_s)

    # ---- observability ----------------------------------------------------

    def _event(self, evt: str, **fields) -> None:
        """One structured (JSON) log line per server event."""
        self._log(json.dumps({"evt": evt, "t": round(time.time(), 3),
                              **fields}), flush=True)

    def metrics_text(self) -> str:
        """Prometheus text: the server's registry (engine + batcher +
        front end — the same instruments ``/statz`` reads) followed by
        the process-wide resilience counters (retries, emergency saves —
        obs.GLOBAL_REGISTRY). No lock is needed: every instrument
        snapshots under its own leaf lock, and the gauge refresh only
        reads the batcher's occupancy."""
        from picotron_tpu.obs import GLOBAL_REGISTRY

        # depth/occupancy gauges are point-in-time reads: refresh them so
        # a scraper that never touches /statz still sees current values
        self._batcher.refresh_gauges()
        # the prefill-queue depth a disaggregated router watches: on a
        # prefill worker every queued request IS a waiting prefill
        self.obs.registry.gauge(
            "picotron_prefill_queue_depth",
            "requests waiting for a prefill slot").set(
                self._batcher.queue_depth)
        return self.obs.registry.prometheus() + GLOBAL_REGISTRY.prometheus()

    def trace_json(self) -> dict:
        """The process span ring, and the slow rounds' spans pinned beside
        it, as Chrome-trace JSON."""
        return self.obs.tracer.chrome_trace()

    def healthy(self) -> bool:
        return not (self.stalled or self.dead)

    def ready(self) -> bool:
        return not (self.draining or self.stalled or self.dead)

    def stats(self) -> dict:
        # bounded wait: the stats an operator checks DURING a dispatch
        # stall must answer, degraded, rather than park on the lock the
        # stalled loop is holding
        if self._mu.acquire(timeout=1.0):
            try:
                d = self._batcher.stats()
            finally:
                self._mu.release()
        else:
            d = {"snapshot": "partial (dispatch in progress)"}
        # the stall judge answers without the lock: what an operator asks
        # for DURING a stall. The watchdog's own count of episodes past
        # stall_timeout_s (once /statz ``stalls`` itself) rides inside.
        d["stalls"] = {**self.obs.stalls.stats(),
                       "watchdog_episodes": self.stalls}
        with self._rej_mu:
            d["rejected"] = dict(self.rejections)
        d["weight_bytes"] = self.weight_bytes
        d["weight_dtype"] = self.engine.weight_dtype
        d["role"] = self.role
        if self.tenants is not None:
            d["tenant_names"] = self.tenants.names()
        d["draining"] = self.draining
        d["dead"] = self.dead
        d["stalled"] = self.stalled
        d["uptime_s"] = round(time.monotonic() - self._start_t, 3)
        return d


def _r(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 6)


MAX_BODY_BYTES = 8 << 20  # request-body cap: reject before allocating


class _Handler(BaseHTTPRequestHandler):
    # close-delimited streaming: HTTP/1.0 responses end at connection close,
    # which lets the token stream flush incrementally with zero framing code
    protocol_version = "HTTP/1.0"

    @property
    def front(self) -> FrontEnd:
        return self.server.front

    def log_message(self, *a):  # the front end's JSON lines replace these
        pass

    def _json(self, status: int, payload: dict, headers=()) -> None:
        body = (json.dumps(payload) + "\n").encode()
        self.send_response(status)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in headers:
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self) -> None:
        f = self.front
        if self.path == "/healthz":
            ok = f.healthy()
            self._json(200 if ok else 503,
                       {"ok": ok, "stalled": f.stalled, "dead": f.dead})
        elif self.path == "/readyz":
            # the body's "state" is the poller's contract: "draining" is
            # GRACEFUL (a router stops placing, breaker untouched) while
            # "stalled"/"dead" are failures — without it, drain and death
            # are indistinguishable 503s (docs/SERVING.md)
            ok = f.ready()
            state = ("dead" if f.dead else "stalled" if f.stalled
                     else "draining" if f.draining else "ready")
            # "role" rides the poller's contract: a router must know a
            # prefill worker from a decode target off the same probe
            self._json(200 if ok else 503,
                       {"ok": ok, "state": state, "role": f.role,
                        "draining": f.draining,
                        "stalled": f.stalled, "dead": f.dead})
        elif self.path == "/statz":
            self._json(200, f.stats())
        elif self.path == "/metrics":
            body = f.metrics_text().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path == "/tracez":
            self._json(200, f.trace_json())
        elif self.path.startswith("/kv/pages"):
            # GET /kv/pages?ids=1,2,3[&tenant=name] — the lookup surface
            # for short prompts and manual inspection (POST takes a JSON
            # body for long ones)
            from urllib.parse import parse_qs, urlparse

            q = parse_qs(urlparse(self.path).query)
            try:
                ids = [int(t) for t in
                       (q.get("ids", [""])[0]).split(",") if t]
            except ValueError as e:
                self._json(400, {"error": f"bad ids: {e}"})
                return
            try:
                self._json(200, f.kv_pages(
                    ids, tenant=q.get("tenant", [None])[0]))
            except AdmissionError as e:
                self._json(e.status, {"error": e.reason, **e.extra})
        elif self.path.startswith("/kv/prefixes"):
            # GET /kv/prefixes?limit=N — the drain-time cache handoff's
            # enumeration surface (tools/fleet.py)
            from urllib.parse import parse_qs, urlparse

            q = parse_qs(urlparse(self.path).query)
            try:
                limit = int(q.get("limit", ["4"])[0])
            except ValueError as e:
                self._json(400, {"error": f"bad limit: {e}"})
                return
            try:
                self._json(200, f.kv_prefixes(limit))
            except AdmissionError as e:
                self._json(e.status, {"error": e.reason, **e.extra})
        elif self.path == "/tenants":
            try:
                self._json(200, f.tenants_snapshot())
            except AdmissionError as e:
                self._json(e.status, {"error": e.reason, **e.extra})
        else:
            self._json(404, {"error": f"unknown path {self.path}"})

    def do_DELETE(self) -> None:
        # DELETE /tenants/<name> — hot tenant removal (the admin half of
        # POST /tenants); in-flight rows degrade to base output
        if not self.path.startswith("/tenants/"):
            self._json(404, {"error": f"unknown path {self.path}"})
            return
        from urllib.parse import unquote

        name = unquote(self.path[len("/tenants/"):])
        try:
            self._json(200, self.front.tenants_remove(name))
        except AdmissionError as e:
            self._json(e.status, {"error": e.reason, **e.extra})

    def _profilez(self, spec: dict) -> None:
        f = self.front
        try:
            seconds = (float(spec["seconds"]) if "seconds" in spec
                       else None)
        except (TypeError, ValueError) as e:
            self._json(400, {"error": f"bad profilez field: {e}"})
            return
        if seconds is not None and seconds <= 0:
            # a malformed request is the CLIENT's bug: 400, never the
            # 409 that means "a capture is already running"
            self._json(400, {"ok": False,
                             "error": f"seconds must be > 0, got {seconds}"})
            return
        # always timed: this surface has no stop verb
        out = f.profiler.start(
            out_dir=spec.get("dir") or None,
            seconds=seconds if seconds is not None else f.profiler.seconds)
        self._json(200 if out["ok"] else 409, out)

    def do_POST(self) -> None:
        if self.path not in ("/generate", "/profilez", "/kv/export",
                             "/kv/import", "/kv/pages", "/tenants",
                             "/drain"):
            self._json(404, {"error": f"unknown path {self.path}"})
            return
        try:
            n = int(self.headers.get("Content-Length", 0))
        except ValueError as e:
            self._json(400, {"error": f"bad Content-Length: {e}"})
            return
        if n < 0:
            self._json(400, {"error": f"bad Content-Length: {n}"})
            return
        if n > MAX_BODY_BYTES:
            # the declared length drives the read: cap it BEFORE allocating,
            # or one client buys arbitrary memory ahead of any admission check
            self._json(413, {"error": f"request body too large "
                                      f"({n} > {MAX_BODY_BYTES} bytes)"})
            return
        try:
            spec = json.loads(self.rfile.read(n) or b"{}")
        except (ValueError, json.JSONDecodeError) as e:
            self._json(400, {"error": f"bad request body: {e}"})
            return
        if not isinstance(spec, dict):
            # valid JSON that is not an object ('[]', 'null', '3') must be
            # a 400, not an AttributeError-dropped connection
            self._json(400, {"error": "request body must be a JSON object"})
            return
        if self.path == "/profilez":
            self._profilez(spec)
            return
        if self.path == "/drain":
            try:
                self._json(202, self.front.drain())
            except AdmissionError as e:
                self._json(e.status, {"error": e.reason, **e.extra})
            return
        if self.path in ("/kv/export", "/kv/import", "/kv/pages",
                         "/tenants"):
            try:
                if self.path == "/kv/export":
                    out = self.front.kv_export(spec)
                elif self.path == "/kv/import":
                    out = self.front.kv_import(spec.get("kv") or spec)
                elif self.path == "/tenants":
                    out = self.front.tenants_add(spec)
                else:
                    out = self.front.kv_pages(spec.get("ids"),
                                              tenant=spec.get("tenant"))
            except AdmissionError as e:
                headers = ([("Retry-After", str(e.retry_after))]
                           if e.retry_after else [])
                self._json(e.status, {"error": e.reason, **e.extra},
                           headers)
                return
            self._json(200, out)
            return
        try:
            uid, waiter = self.front.submit(spec)
        except AdmissionError as e:
            headers = ([("Retry-After", str(e.retry_after))]
                       if e.retry_after else [])
            self._json(e.status, {"error": e.reason, "shed": True,
                                  **e.extra}, headers)
            return
        # client-supplied correlation id, echoed on every response row
        # (falling back to the server uid): the observable a router's
        # replay dedup keys off end to end
        rid = str(spec.get("request_id") or uid)
        if spec.get("stream"):
            self._stream(uid, waiter, rid)
        else:
            res = self._await_result(waiter)
            payload = {"uid": uid, "request_id": rid,
                       "tokens": list(res.tokens),
                       "finish_reason": res.finish_reason,
                       "queue_wait_s": _r(res.queue_wait_s),
                       "ttft_s": _r(res.ttft_s)}
            if res.finish_reason == "shed":
                self._json(503, payload, [("Retry-After", "5")])
            elif res.finish_reason == "error":
                self._json(500, payload)
            else:
                self._json(200, payload)

    def _await_result(self, waiter: _Waiter):
        while True:
            kind, val = waiter.events.get()
            if kind == "done":
                return val

    def _stream(self, uid: str, waiter: _Waiter, request_id: str) -> None:
        self.send_response(200)
        self.send_header("Content-Type", "application/x-ndjson")
        self.end_headers()

        def emit(text):
            # one write (the handler's wfile is unbuffered: one send) an
            # event, however many rows it holds
            self.wfile.write(text.encode())
            self.wfile.flush()

        # a token row is json.dumps of {"event", "uid", "request_id",
        # "token"} in that order; all but the number is the stream's own
        head = json.dumps({"event": "token", "uid": uid,
                           "request_id": request_id})[:-1] + ', "token": '
        while True:
            kind, val = waiter.events.get()
            try:
                if kind == "tokens":
                    emit("".join(f"{head}{t}}}\n" for t in val))
                    continue
                emit(json.dumps({"event": "done", "uid": uid,
                                 "request_id": request_id,
                                 "tokens": list(val.tokens),
                                 "finish_reason": val.finish_reason,
                                 "queue_wait_s": _r(val.queue_wait_s),
                                 "ttft_s": _r(val.ttft_s)}) + "\n")
            except (BrokenPipeError, ConnectionResetError):
                # client went away: generation continues (the batcher owns
                # the request; its per-request timeout_s bounds the waste),
                # keep draining events so the waiter's queue empties
                if kind == "done":
                    return
                continue
            if kind == "done":
                return


class _HTTPServer(ThreadingHTTPServer):
    # a closed loop's clients all connect at once. Past the listen backlog
    # (socketserver's default: 5) the kernel drops a SYN, and the client
    # sends it again after 1, 3, 7, 15, 31, 63 s: of 128 clients one reached
    # the server 63 s behind the others in two runs of six (PERF.md, PR 56)
    request_queue_size = 1024


class Server:
    """FrontEnd + ThreadingHTTPServer, both on background threads. The
    embedding entry point for the CLI, the smoke drive, and the tests."""

    def __init__(self, engine, params, *, host: str = "127.0.0.1",
                 port: int = 0, **front_kw):
        self.front = FrontEnd(engine, params, **front_kw)
        self.httpd = _HTTPServer((host, port), _Handler)
        self.httpd.front = self.front
        self.port = self.httpd.server_address[1]
        self._http_thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self.front.start()
        # once the dispatch loop finishes a drain, stop accepting sockets
        self.front._on_drained = lambda: threading.Thread(
            target=self.httpd.shutdown, daemon=True).start()
        self._http_thread = threading.Thread(
            target=self.httpd.serve_forever, name="serve-http", daemon=True)
        self._http_thread.start()

    def drain_and_join(self, timeout: Optional[float] = None) -> None:
        self.front.begin_drain()
        self.front.join(timeout)
        self.httpd.shutdown()
        if self._http_thread is not None:
            self._http_thread.join(timeout)
        self.httpd.server_close()


# --------------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------------- #


def _build_engine_and_params(args):
    from picotron_tpu.config import Config
    from picotron_tpu.inference import InferenceEngine
    from picotron_tpu.tools.generate import SMOKE_CONFIG, _load_weights
    from picotron_tpu.train import _ensure_devices

    if args.smoke:
        cfg = Config.from_dict(SMOKE_CONFIG)
        args.random_init = True
    elif args.config:
        with open(args.config) as f:
            cfg = Config.from_dict(json.load(f))
    else:
        raise SystemExit("pass --config (or --smoke)")
    if not (args.load_path or args.hf_path or args.random_init):
        raise SystemExit("pass one of --load-path / --hf-path / "
                         "--random-init")
    if getattr(args, "kv_layout", None):
        cfg.inference.kv_layout = args.kv_layout
    if getattr(args, "role", None):
        cfg.inference.role = args.role
    if getattr(args, "overlap", False):
        # zero-bubble pipelined scheduling (docs/INFERENCE.md
        # "Overlapped scheduling"): forces the per-slot key schedule,
        # token streams stay bit-identical to the default
        cfg.inference.overlap = True
    if getattr(args, "kv_layout", None) or getattr(args, "role", None):
        # either override can break the role/layout invariant (e.g.
        # --kv-layout contiguous on a config whose role is prefill)
        cfg.validate()
    _ensure_devices(cfg)
    from picotron_tpu.resilience.chaos import ServingChaos

    chaos = ServingChaos(cfg.resilience)
    hooks = chaos if chaos.active else None
    adapters, registry = _build_tenancy(cfg, args)
    engine = InferenceEngine(cfg, slots=args.slots,
                             max_seq_len=args.max_seq_len, hooks=hooks,
                             adapters=adapters)
    params = _load_weights(args, cfg, engine)
    return cfg, engine, params, registry


def _build_tenancy(cfg, args):
    """(AdapterPack, TenantRegistry) from inference.tenancy + the
    --tenant-manifest override, or (None, None) when no tenancy is
    configured (the bit-pinned single-tenant default: no pack, so the
    compiled programs are byte-identical to the pre-tenancy engine).
    The pack is built whenever a registry is — even all-rank-0 tenants
    may hot-add an adapter tenant later, and capacity must exist from
    the start (add/remove never recompiles)."""
    tcfg = cfg.inference.tenancy
    manifest = getattr(args, "tenant_manifest", "") or tcfg.manifest
    if not manifest and not tcfg.tenants:
        return None, None
    from picotron_tpu.inference import tenancy

    pack = tenancy.AdapterPack(cfg.model, slots=tcfg.adapter_slots,
                               rank=tcfg.adapter_rank)
    if manifest:
        registry = tenancy.TenantRegistry.from_manifest(manifest, pack)
    else:
        registry = tenancy.TenantRegistry(pack)
    for entry in tcfg.tenants:  # config extends (or replaces) a manifest
        registry.add(tenancy.Tenant.from_dict(entry))
    return pack, registry


def _post(port: int, spec: dict, stream: bool = False):
    """Minimal stdlib client for the smoke drive: returns (status,
    parsed-JSON body) or, streaming, (status, [parsed NDJSON events])."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    conn.request("POST", "/generate", json.dumps(spec),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    if stream:
        lines = [json.loads(l) for l in resp.read().splitlines() if l]
        out = (resp.status, lines)
    else:
        out = (resp.status, json.loads(resp.read() or b"{}"))
    conn.close()
    return out


def _get(port: int, path: str):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    resp = conn.getresponse()
    out = (resp.status, json.loads(resp.read() or b"{}"))
    conn.close()
    return out


def _profilez_post(port: int, spec: dict):
    """POST /profilez (stdlib client): (status, parsed body)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("POST", "/profilez", json.dumps(spec),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = (resp.status, json.loads(resp.read() or b"{}"))
    conn.close()
    return out


def _get_text(port: int, path: str):
    """GET a non-JSON surface (/metrics): (status, text)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    resp = conn.getresponse()
    out = (resp.status, resp.read().decode("utf-8", errors="replace"))
    conn.close()
    return out


def _smoke(server: Server, obs_dump: str = "") -> int:
    """The `make serve-smoke` drive: health, one POST, one streamed POST,
    the observability surfaces (/metrics agreeing with /statz, a complete
    request chain in /tracez, one timed /profilez capture), then a
    SIGTERM drain with full accounting. ``obs_dump`` saves the trace and
    metrics page there for `make obs-smoke`'s trace_dump gate. Returns an
    exit code."""
    import os
    import signal

    port = server.port
    fail = []

    def check(name, ok):
        print(f"serve-smoke: {name}: {'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            fail.append(name)

    check("healthz", _get(port, "/healthz")[0] == 200)
    check("readyz", _get(port, "/readyz")[0] == 200)

    spec = {"prompt": [1, 2, 3, 4, 5], "max_new_tokens": 8}
    st, body = _post(port, spec)
    check("generate", st == 200 and len(body["tokens"]) == 8
          and body["finish_reason"] == "length")

    st, events = _post(port, {**spec, "stream": True}, stream=True)
    done = [e for e in events if e["event"] == "done"]
    toks = [e["token"] for e in events if e["event"] == "token"]
    check("stream", st == 200 and len(done) == 1
          and done[0]["tokens"] == toks
          and done[0]["tokens"] == body["tokens"])  # greedy: deterministic

    # ---- observability surfaces (docs/OBSERVABILITY.md) ----
    from picotron_tpu.obs.metrics import parse_prometheus
    from picotron_tpu.tools import trace_dump

    st, stats = _get(port, "/statz")
    mst, mtext = _get_text(port, "/metrics")
    prom = parse_prometheus(mtext)
    check("metrics_agrees_with_statz",
          mst == 200
          and prom.get('picotron_requests_total{state="completed"}')
          == stats.get("completed")
          and prom.get('picotron_generated_tokens_total')
          == stats.get("generated_tokens"))
    tst, trace = _get(port, "/tracez")
    chains = trace_dump.request_chains(trace)
    check("tracez_request_chain",
          tst == 200 and not trace_dump.validate(trace)
          and any(c["complete"] for c in chains.values()))
    if stats.get("overlap", {}).get("enabled"):
        # zero-bubble gates (--overlap): the issue-to-issue gap collapses
        # under a full pipeline — strictly below the per-round host sync
        # it used to serialize behind — and every overlap span links
        # round N's sync stage inside round N+1's dispatch window
        ov = stats["overlap"]
        gap = (ov.get("dispatch_gap_s") or {}).get("p50")
        check("overlap_gap_lt_host_sync",
              gap is not None
              and gap < max(stats.get("last_host_sync_s", 0.0), 1e-6))
        oc = trace_dump.overlap_chain(trace)
        check("overlap_span_chain",
              oc["linked"] >= 1 and not oc["errors"])
    if obs_dump:
        os.makedirs(obs_dump, exist_ok=True)
        with open(os.path.join(obs_dump, "trace.json"), "w") as f:
            json.dump(trace, f)
        with open(os.path.join(obs_dump, "metrics.txt"), "w") as f:
            f.write(mtext)
    prof_dir = os.path.join(obs_dump or "/tmp", "serve-smoke-profile")
    pst, pbody = _profilez_post(port, {"seconds": 0.2, "dir": prof_dir})
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and server.front.profiler.running:
        time.sleep(0.05)
    check("profilez",
          pst == 200 and pbody.get("ok")
          and server.front.profiler.captures >= 1
          and os.path.isdir(prof_dir) and os.listdir(prof_dir))

    # drain: one slow request in flight + SIGTERM -> it finishes, the
    # server stops admitting, and the exit is clean
    slow: dict = {}

    def bg():
        slow["resp"] = _post(port, {"prompt": [7, 8, 9],
                                    "max_new_tokens": 24})

    t = threading.Thread(target=bg)
    t.start()
    # wait until the slow request actually holds a slot (a fixed sleep is a
    # race on a loaded host: still-queued at SIGTERM means it gets shed and
    # the drain checks below fail spuriously); "completed" covers the other
    # race, where the tiny model finishes it before we observe the slot
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        s = _get(port, "/statz")[1]
        if s.get("active_slots", 0) > 0 or s.get("completed", 0) >= 3:
            break
        time.sleep(0.02)
    else:
        check("slow_request_admitted", False)
    os.kill(os.getpid(), signal.SIGTERM)
    server.front.join(timeout=120)
    check("drain_finished", server.front.stopped.is_set())
    t.join(timeout=120)
    st, body = slow.get("resp", (None, {}))
    check("inflight_served_through_drain",
          st == 200 and body.get("finish_reason") == "length")
    stats = server.front.stats()
    # every admitted request reached a terminal state and nothing leaked
    terminal = stats["completed"] + stats["expired"] + stats["errored"]
    check("accounting", terminal == stats["admitted"] == 3
          and stats["queued"] == 0 and stats["active_slots"] == 0)
    check("no_stalls", stats["stalls"]["watchdog_episodes"] == 0)
    return 1 if fail else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="HTTP serving front end over the continuous batcher "
                    "(admission control, load shedding, graceful drain)")
    ap.add_argument("--config", help="training config.json (model shape, tp)")
    ap.add_argument("--load-path", default="", help="orbax checkpoint dir")
    ap.add_argument("--hf-path", default="", help="HF safetensors file/dir")
    ap.add_argument("--random-init", action="store_true",
                    help="seed-derived random weights (smoke)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="0 = ephemeral (printed at startup)")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=None)
    ap.add_argument("--role", choices=("prefill", "decode", "both"),
                    default=None,
                    help="disaggregated serving role (overrides "
                         "inference.role; prefill/decode require "
                         "inference.kv_layout: paged)")
    ap.add_argument("--kv-layout", choices=("contiguous", "paged"),
                    default=None,
                    help="KV cache layout override (paged is required "
                         "for any role but 'both')")
    ap.add_argument("--overlap", action="store_true",
                    help="zero-bubble scheduling: issue dispatch N+1 "
                         "before syncing dispatch N (sets "
                         "inference.overlap; bit-identical streams)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded wait queue: excess submissions get 503 "
                         "(default: 64, or 2 x slots where that is more)")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="cap on live prompt+generation tokens (default: "
                         "slots * max_seq_len); excess gets 429")
    ap.add_argument("--default-timeout-s", type=float, default=None,
                    help="per-request wall-clock deadline when the request "
                         "does not set one (finish_reason 'timeout')")
    ap.add_argument("--stall-timeout", type=float, default=60.0,
                    help="dispatch-stall watchdog threshold (0 = off); a "
                         "stall flips /healthz to 503")
    ap.add_argument("--tenant-manifest", default="",
                    help="JSON tenant manifest ({\"tenants\": [...]}, "
                         "inference/tenancy.py) — overrides "
                         "inference.tenancy.manifest; enables the "
                         "multi-tenant plane (adapter pack, /tenants "
                         "admin endpoint, per-tenant quotas/SLOs)")
    ap.add_argument("--smoke", action="store_true",
                    help="built-in tiny CPU model + scripted client drive "
                         "(the `make serve-smoke` target)")
    ap.add_argument("--obs-dump", default="",
                    help="smoke only: save the drive's /tracez JSON and "
                         "/metrics page into this dir (the `make "
                         "obs-smoke` target validates them with "
                         "tools/trace_dump.py)")
    args = ap.parse_args(argv)

    from picotron_tpu.utils import enable_compile_cache

    enable_compile_cache()  # before the first compile
    cfg, engine, params, registry = _build_engine_and_params(args)

    server = Server(
        engine, params, host=args.host,
        port=0 if args.smoke else args.port, seed=args.seed,
        max_queue=args.max_queue, token_budget=args.token_budget,
        default_timeout_s=args.default_timeout_s,
        stall_timeout_s=args.stall_timeout, tenants=registry)
    # SIGTERM/SIGINT -> graceful drain (the PreemptionGuard pattern: first
    # signal is cooperative, second aborts). SIGUSR2 -> one timed
    # jax.profiler capture into obs.profile_dir (the POST /profilez
    # trigger without a client). Installed on the main thread.
    server.front.guard.install()
    from picotron_tpu.obs import install_sigusr2

    install_sigusr2(server.front.profiler)
    server.start()
    server.front._event(
        "serving", port=server.port, slots=engine.slots,
        max_seq_len=engine.max_seq_len, max_queue=server.front.max_queue,
        token_budget=server.front.token_budget,
        attend_impl=engine.attend_impl, role=server.front.role,
        kv=str(engine.cache_dtype), kv_layout=engine.kv_layout,
        tp=engine.topo.tp_size,
        tenants=(registry.names() if registry is not None else None))

    if args.smoke:
        rc = _smoke(server, obs_dump=args.obs_dump)
        print(f"serve-smoke: {'PASS' if rc == 0 else 'FAIL'}", flush=True)
        return rc

    # foreground: wait for the drain (SIGTERM) to complete. Exit 0 ONLY
    # for a clean drain — a dead dispatch loop must exit nonzero so a
    # supervisor (tools/supervise.py --serve) restarts the replica
    # instead of reading the death as an intentional shutdown.
    try:
        while not server.front.stopped.is_set():
            server.front.join(timeout=1.0)
    except KeyboardInterrupt:
        pass  # second signal: abort now
    return 1 if server.front.dead else 0


if __name__ == "__main__":
    sys.exit(main())
