"""Multi-replica serving fabric: one logical endpoint over N serve.py replicas.

    python -m picotron_tpu.tools.router --replica 10.0.0.1:8000 \
        --replica 10.0.0.2:8000 --port 9000

`tools/serve.py` made ONE replica chaos-survivable; this is the layer that
makes a FLEET of them look like one endpoint (docs/SERVING.md
"Multi-replica fabric"). Stdlib only, like the front end it fronts. Three
responsibilities:

**Placement** — each request is scored onto a replica by prefix affinity
plus load. The affinity key is the longest page-aligned prompt prefix
(``RouterConfig.affinity_page_len``; match the fleet's
``inference.kv_page_len``): requests sharing a system prompt rendezvous-
hash to the same replica, which already holds those radix-cache pages, so
the shared prefix is prefilled once per CLUSTER instead of once per
request. The affinity pick wins only while its load score — queue depth +
router-inflight, active slots, KV pool occupancy, and TTFT p95, every
term scraped from the replica's own ``/metrics`` (the PR-10 instruments)
— stays within ``affinity_load_slack`` of the least-loaded candidate;
past that, least-loaded wins. Replicas whose last good scrape is older
than ``scrape_stale_s`` fall out of the candidate set entirely: unknown
load is unplaceable load.

**Failure handling** — a prober thread per replica walks
``/healthz`` + ``/readyz`` + ``/metrics`` on ``probe_interval_s``. A
readyz 503 whose body says ``{"state": "draining"}`` is GRACEFUL: the
replica leaves the candidate set but its circuit breaker is untouched
(that is the drain-vs-dead distinction serve.py's readyz body exists
for). Hard failures (unreachable, healthz 503, readyz stalled/dead)
count consecutively: at ``breaker_failures`` the breaker opens and the
prober switches to an exponential reprobe ladder driven by
``resilience.retry``; the first successful reprobe flips half-open,
where ONE trial request (or ``breaker_failures`` consecutive clean
probes) decides closed vs open again. A scrape-only failure is SOFT —
health state still updates, but the scrape goes stale and the replica
drops out of placement without tripping the breaker. When no replica is
eligible the router answers 503 with ``Retry-After``.

**Prefill/decode disaggregation** (``RouterConfig.disagg``,
docs/SERVING.md "Disaggregated prefill/decode") — when the fleet holds
``inference.role: prefill`` replicas, each prompt's prefill routes to
its affinity prefill worker (``POST /kv/export``), the finished KV pool
pages ride to the least-loaded DECODE placement inside the ``/generate``
body (the replica seats them + the first token with zero prefill
dispatches), and the token stream splices to the client as usual. A
prefill worker dying mid-export or a page stream severed mid-transfer
falls back to self-prefill at the decode placement — nothing was
streamed, so the client cannot tell. Prefill-only replicas are never
decode candidates (they would otherwise score as idle decode targets).
On a plain placement that escaped its affinity owner,
``RouterConfig.prefix_fetch`` pulls the owner's longest cached prefix
(``/kv/pages`` -> ``/kv/import``) so shared prefixes still prefill once
per cluster.

**Mid-stream failover replay** — the router always streams from the
replica and records every token it delivers to the client. When a
replica dies mid-stream (connection drop, torn NDJSON row, 5xx, a
``finish_reason: "error"`` from a dying dispatch loop), the router
re-submits the ORIGINAL prompt *plus the already-delivered tokens* as
the new prompt to a surviving replica with the token budget reduced by
what was delivered. The replayed prefix is prompt, not generation, on
the new replica — nothing is re-emitted — and the spliced stream hands
the client every token exactly once. Greedy requests are bit-identical
to an unfaulted run (the continuation is conditioned on exactly the
prefix the client already holds); stochastic requests are
prefix-consistent, not bit-identical (the surviving replica draws fresh
PRNG keys — docs/SERVING.md spells out the caveat). Failovers are
bounded by ``replay_budget``; refused placements (shed, drain-shed) by
``place_attempts``.

Client surface (mirrors serve.py): ``POST /generate`` (same body; adds
``request_id`` passthrough — echoed on every NDJSON row by router and
replica so replay dedup is observable end to end), ``GET /healthz``
``/readyz`` ``/statz`` ``/metrics`` ``/tracez``. Router responses carry
``replays`` / ``attempts`` / ``replica`` so a client can see a failover
happened without losing a token.

``--smoke`` is the ``make router-chaos-smoke`` drive: 2–3 in-process
serve.py replicas + this router + ``resilience.chaos.RouterChaos``
(kill a replica mid-stream, stall healthz past the probe timeout, flap
health, inject scrape failures, drain) with a bit-identical greedy
oracle and full accounting asserts.
"""

from __future__ import annotations

import argparse
import hashlib
import http.client
import json
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Optional
from urllib.parse import unquote

from picotron_tpu.config import RouterConfig
from picotron_tpu.obs import GLOBAL_REGISTRY, Obs
from picotron_tpu.obs.metrics import parse_prometheus
from picotron_tpu.resilience.retry import retry


class DuplicateReplica(ValueError):
    """A dynamic registration named a replica already in the set (the
    admin API's 409, distinct from a malformed spec's 400)."""


class ReplicaFailure(Exception):
    """A hard per-replica failure: unreachable, sick health surface, or a
    broken /generate stream. Feeds the circuit breaker."""


class RouteRefused(Exception):
    """The router-level reject (the fabric's AdmissionError): nothing was
    streamed to the client and the caller turns this into an HTTP
    status + Retry-After."""

    def __init__(self, status: int, reason: str, retry_after: int = 0):
        super().__init__(reason)
        self.status = status
        self.reason = reason
        self.retry_after = retry_after


class _Stopped(Exception):
    """Router shutdown interrupting a prober sleep/backoff ladder."""


# --------------------------------------------------------------------------- #
# pure helpers (unit-tested directly)
# --------------------------------------------------------------------------- #


def prefix_key(prompt, page_len: int,
               tenant: str = "") -> Optional[str]:
    """Affinity key: hash of the longest page-aligned prompt prefix, or
    None when the prompt holds no whole page (nothing the radix cache
    could share — pure least-loaded placement). ``tenant`` salts the
    key exactly as it salts the replica-side radix domains
    (inference/tenancy.py): identical prompts under different tenants
    share no pages, so they must not share an affinity owner's cache
    bank either. Anonymous/base traffic ("") hashes as before."""
    n = (len(prompt) // page_len) * page_len
    if n <= 0:
        return None
    raw = ",".join(str(int(t)) for t in prompt[:n]).encode()
    if tenant:
        raw = tenant.encode() + b"|" + raw
    return hashlib.blake2b(raw, digest_size=8).hexdigest()


def _rendezvous(key: str, name: str) -> int:
    """Highest-random-weight hash: every router instance ranks the same
    replicas identically for one prefix, with no shared state and minimal
    disruption when the replica set changes."""
    h = hashlib.blake2b(f"{key}|{name}".encode(), digest_size=8)
    return int.from_bytes(h.digest(), "big")


def tenant_scrape(prom: dict) -> dict:
    """Per-tenant load surfaced by one /metrics scrape: {tenant:
    {"queue_depth", "active_slots", "ttft_p95"}} off the labeled
    ``picotron_tenant_*`` families (tenancy-less replicas export none —
    an empty dict, and placement scores exactly as before)."""
    import re

    tenants = set()
    for k in prom:
        if k.startswith(("picotron_tenant_queue_depth{",
                         "picotron_tenant_ttft_seconds_count{")):
            m = re.search(r'tenant="([^"]*)"', k)
            if m:
                tenants.add(m.group(1))
    out = {}
    for t in sorted(tenants):
        label = f'tenant="{t}"'
        sub = {k: v for k, v in prom.items() if label in k}
        out[t] = {
            "queue_depth": sub.get(
                f"picotron_tenant_queue_depth{{{label}}}", 0.0),
            "active_slots": sub.get(
                f"picotron_tenant_active_slots{{{label}}}", 0.0),
            "ttft_p95": hist_quantile(
                sub, "picotron_tenant_ttft_seconds", 0.95),
        }
    return out


def hist_quantile(prom: dict, name: str, q: float) -> float:
    """Quantile estimate from a scraped Prometheus histogram: the upper
    bound of the first cumulative bucket covering ``q`` of the count
    (conservative — a bucket bound, not an interpolation). 0.0 when the
    histogram is absent or empty."""
    pts = []
    total = None
    prefix = f"{name}_bucket{{"
    for k, v in prom.items():
        if not k.startswith(prefix):
            continue
        i = k.find('le="')
        le = k[i + 4:k.rindex('"')]
        if le == "+Inf":
            total = v
        else:
            pts.append((float(le), v))
    if not total or not pts:
        return 0.0
    pts.sort()
    target = q * total
    for le, cum in pts:
        if cum >= target:
            return le
    return pts[-1][0]


# --------------------------------------------------------------------------- #
# transport (all failures normalized to ReplicaFailure)
# --------------------------------------------------------------------------- #

_TRANSPORT_ERRORS = (OSError, http.client.HTTPException, ValueError)


def _get_json(host: str, port: int, path: str, timeout: float) -> tuple:
    try:
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read() or b"{}")
        finally:
            conn.close()
    except _TRANSPORT_ERRORS as e:
        raise ReplicaFailure(
            f"GET {path}: {type(e).__name__}: {e}") from e


def _get_text(host: str, port: int, path: str, timeout: float) -> tuple:
    try:
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, resp.read().decode("utf-8", errors="replace")
        finally:
            conn.close()
    except _TRANSPORT_ERRORS as e:
        raise ReplicaFailure(
            f"GET {path}: {type(e).__name__}: {e}") from e


def _post_json(host: str, port: int, path: str, payload: dict,
               timeout: float, on_read=None) -> tuple:
    """POST a JSON body, read a JSON response. ``on_read`` fires between
    the response head and the body read — the chaos hook that severs a
    page stream mid-transfer."""
    try:
        conn = http.client.HTTPConnection(host, port, timeout=timeout)
        try:
            conn.request("POST", path, json.dumps(payload),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            if on_read is not None:
                on_read()
            return resp.status, json.loads(resp.read() or b"{}")
        finally:
            conn.close()
    except _TRANSPORT_ERRORS as e:
        raise ReplicaFailure(
            f"POST {path}: {type(e).__name__}: {e}") from e


# --------------------------------------------------------------------------- #
# replica record
# --------------------------------------------------------------------------- #


class Replica:
    """Per-replica state. Every mutable field is guarded by ``_mu`` — a
    LEAF lock (picolint PICO-C001/C003): taken for pure state reads and
    transitions only, never while doing I/O or waiting on another lock."""

    def __init__(self, name: str, host: str, port: int):
        self.name = name
        self.host = host
        self.port = int(port)
        self._mu = threading.Lock()
        # set when this replica leaves the set (deregistered by the admin
        # API) or the router stops: the prober's sleep/ladder waits on it,
        # so removal interrupts even a breaker-open reprobe backoff
        self.gone = threading.Event()
        self._prober: Optional[threading.Thread] = None
        self.breaker = "closed"  # closed | open | half_open
        self.fails = 0  # consecutive hard failures
        self.okays = 0  # consecutive clean probes (half-open recovery)
        self.trial = False  # half-open: one live trial request at a time
        self.ready = False
        self.draining = False
        self.role = "both"  # from the readyz body: prefill|decode|both
        self.scrape: dict = {}  # parsed load terms from /metrics
        self.scrape_t = float("-inf")  # monotonic time of last good scrape
        self.inflight = 0  # router-placed requests currently streaming

    def snapshot(self, now: float) -> dict:
        with self._mu:
            age = now - self.scrape_t
            return {
                "addr": f"{self.host}:{self.port}",
                "breaker": self.breaker,
                "ready": self.ready,
                "role": self.role,
                "draining": self.draining,
                "consecutive_failures": self.fails,
                "inflight": self.inflight,
                "scrape_age_s": None if age == float("inf") else round(age, 3),
                "scrape": dict(self.scrape),
            }


class Router:
    """Placement + breaker + failover brain (no HTTP server of its own —
    ``RouterServer`` adds that). Prober threads are started by
    ``start()``; the request path is driven by ``route()`` from any
    number of caller threads.

    Locking discipline (picolint PICO-C001–C004): each ``Replica._mu``
    and the counter-dict lock ``_ctr_mu`` are leaf locks — taken last,
    held only across pure state transitions, never across HTTP calls,
    sleeps, or each other. Registry instruments carry their own internal
    leaf locks."""

    def __init__(self, replicas, cfg: Optional[RouterConfig] = None, *,
                 obs: Optional[Obs] = None, chaos=None, log=print,
                 clock=time.monotonic, allow_empty: bool = False):
        self.cfg = cfg or RouterConfig()
        self.cfg.validate()
        self.replicas: dict = {}
        for spec in replicas:
            name, host, port = self._parse_spec(spec)
            if name in self.replicas:
                raise ValueError(f"duplicate replica name {name!r}")
            self.replicas[name] = Replica(name, host, port)
        if not self.replicas and not allow_empty:
            # allow_empty is the elastic bootstrap (tools/fleet.py): the
            # fleet controller starts an empty router and registers
            # workers through the admin API as they come up
            raise ValueError("router needs at least one replica")
        self.chaos = chaos
        self.obs = obs or Obs(enabled=True)
        self.registry = self.obs.registry
        self._log = log
        self._clock = clock
        # requests by terminal state; CounterDict writes are serialized by
        # the leaf lock _ctr_mu (handler threads finish concurrently)
        self.requests = self.registry.counter_dict(
            "picotron_router_requests_total",
            ("completed", "failed", "shed", "client_error", "abandoned"),
            help="routed requests by terminal state", label="state")
        self._ctr_mu = threading.Lock()
        self._replays = self.registry.counter(
            "picotron_router_replays_total",
            "mid-stream failovers replayed onto a surviving replica")
        self._placement_retries = self.registry.counter(
            "picotron_router_placement_retries_total",
            "placements refused (shed/unreachable) and retried elsewhere")
        self._route_hist = self.registry.histogram(
            "picotron_router_route_seconds", "accept -> terminal response")
        # disaggregation plane: handoff round trips (prefill worker ->
        # router -> decode worker) and cross-replica prefix fetches
        self._handoff_hist = self.registry.histogram(
            "picotron_router_handoff_seconds",
            "/kv/export round trip incl. the remote prefill")
        self._handoff_bytes = self.registry.counter(
            "picotron_router_handoff_bytes_total",
            "raw KV page bytes relayed through handoffs")
        self._handoffs = self.registry.counter_dict(
            "picotron_router_handoffs_total",
            ("served", "fallback"),
            help="prefill/decode handoffs by outcome", label="outcome")
        self._prefix_fetches = self.registry.counter_dict(
            "picotron_router_prefix_fetches_total",
            ("hit", "miss", "error"),
            help="cross-replica prefix-cache fetches by outcome",
            label="outcome")
        self._rid_mu = threading.Lock()
        self._rid_seq = 0
        self._stop = threading.Event()
        # replica-set mutation lock (leaf: pure dict copy-and-swap under
        # it, never I/O, never another lock). Reads DON'T take it: every
        # reader iterates whatever dict object self.replicas bound at
        # that moment, and mutations swap in a fresh dict (copy-on-write)
        # rather than mutating the one readers may be iterating.
        self._set_mu = threading.Lock()
        self._started = False
        self._threads: list = []
        self._start_t = clock()

    @staticmethod
    def _parse_spec(spec) -> tuple:
        """(name, host, port) from a replica spec: "host:port" (the name
        IS the address) or a (name, host, port) tuple. Raises ValueError
        on malformed input — the admin API's 400."""
        if isinstance(spec, str):
            host, _, port = spec.rpartition(":")
            if not host or not port:
                raise ValueError(
                    f"replica spec must be HOST:PORT, got {spec!r}")
            spec = (f"{host}:{port}", host, port)
        name, host, port = spec
        return str(name), str(host), int(port)

    # ---- lifecycle --------------------------------------------------------

    def start(self) -> None:
        with self._set_mu:
            self._started = True
            reps = list(self.replicas.values())
        for rep in reps:
            self._spawn_prober(rep)

    def _spawn_prober(self, rep: Replica) -> None:
        t = threading.Thread(target=self._probe_loop, args=(rep,),
                             name=f"router-probe-{rep.name}",
                             daemon=True)
        rep._prober = t
        with self._set_mu:
            self._threads.append(t)
        t.start()

    def stop(self) -> None:
        self._stop.set()
        with self._set_mu:
            reps = list(self.replicas.values())
            threads = list(self._threads)
        for rep in reps:
            rep.gone.set()  # wake probers parked in per-replica sleeps
        for t in threads:
            t.join(timeout=10)

    # ---- dynamic replica set (the fleet controller's admin surface) -------

    def add_replica(self, spec) -> Replica:
        """Register one replica at runtime (the POST /replicas surface).
        The set swap is copy-on-write under ``_set_mu`` so in-progress
        candidate scans never see a mutating dict; the new replica gets
        its prober thread immediately when the router is running. The
        rendezvous hash re-ranks automatically — affinity owners are
        recomputed per placement over the live set. Raises
        ``DuplicateReplica`` (409) on a name collision, ``ValueError``
        (400) on a malformed spec."""
        name, host, port = self._parse_spec(spec)
        rep = Replica(name, host, port)
        with self._set_mu:
            if name in self.replicas:
                raise DuplicateReplica(f"replica {name!r} already "
                                       f"registered")
            replicas = dict(self.replicas)
            replicas[name] = rep
            self.replicas = replicas
            started = self._started
        if started:
            self._spawn_prober(rep)
        self.registry.counter(
            "picotron_router_replica_set_total",
            "dynamic replica-set mutations", op="add").inc()
        self._event("replica_add", replica=name, addr=f"{host}:{port}")
        return rep

    def remove_replica(self, name: str, join_timeout: float = 10.0) -> dict:
        """Deregister one replica at runtime (the DELETE /replicas/<name>
        surface). Safe mid-stream: in-flight routes hold the Replica
        OBJECT, which stays valid — they finish (or fail over) on their
        own; only new placements stop seeing it. The prober thread is
        woken through ``rep.gone`` (it interrupts even a breaker-open
        backoff ladder) and joined, and the breaker/inflight state dies
        with the object — nothing leaks. Raises KeyError when unknown
        (the admin API's 404). Returns the final snapshot."""
        with self._set_mu:
            rep = self.replicas.get(name)
            if rep is None:
                raise KeyError(f"unknown replica {name!r}")
            replicas = dict(self.replicas)
            del replicas[name]
            self.replicas = replicas
        rep.gone.set()
        t = rep._prober
        if t is not None:
            t.join(timeout=join_timeout)
            with self._set_mu:
                if t in self._threads:
                    self._threads.remove(t)
        self.registry.counter(
            "picotron_router_replica_set_total",
            "dynamic replica-set mutations", op="remove").inc()
        self._event("replica_remove", replica=name,
                    prober_joined=t is None or not t.is_alive())
        return rep.snapshot(self._clock())

    def wait_eligible(self, n: int = 1, timeout: float = 30.0) -> bool:
        """Block until >= n replicas are placeable (startup convenience for
        the CLI and the smoke drive)."""
        deadline = self._clock() + timeout
        while self._clock() < deadline:
            if len(self._eligible()) >= n:
                return True
            if self._stop.wait(0.02):
                return False
        return False

    def _sleep(self, seconds: float, rep: Optional[Replica] = None) -> None:
        """Interruptible sleep. With ``rep``, waits on that replica's
        ``gone`` event so a deregistration wakes its prober even out of
        a breaker-open backoff ladder; either wake source (gone or
        router stop) raises ``_Stopped``."""
        ev = self._stop if rep is None else rep.gone
        if ev.wait(seconds) or self._stop.is_set():
            raise _Stopped()

    def _event(self, evt: str, **fields) -> None:
        self._log(json.dumps({"evt": evt, "t": round(time.time(), 3),
                              **fields}), flush=True)

    def _next_rid(self) -> str:
        with self._rid_mu:
            self._rid_seq += 1
            return f"rt{self._rid_seq}"

    # ---- probing + breaker ------------------------------------------------

    def _probe_loop(self, rep: Replica) -> None:
        try:
            while not self._stop.is_set() and not rep.gone.is_set():
                try:
                    self._probe_once(rep)
                except ReplicaFailure as e:
                    if self._probe_fail(rep, str(e)):
                        self._reprobe_open(rep)
                        continue
                self._sleep(self.cfg.probe_interval_s, rep)
        except _Stopped:
            pass

    def _probe_once(self, rep: Replica) -> None:
        """One probe cycle: hard failures (unreachable/sick healthz/
        stalled-or-dead readyz) raise ReplicaFailure; a drain is graceful
        (ready=False, breaker untouched); a scrape failure is soft (the
        scrape goes stale, placement drops the replica, no breaker
        action). All I/O happens before any lock is taken."""
        t = self.cfg.probe_timeout_s
        st, _ = _get_json(rep.host, rep.port, "/healthz", t)
        if st != 200:
            raise ReplicaFailure(f"{rep.name}: healthz {st}")
        st, body = _get_json(rep.host, rep.port, "/readyz", t)
        draining = (body.get("state") == "draining"
                    or bool(body.get("draining")))
        role = body.get("role") or "both"
        if st != 200 and not draining:
            raise ReplicaFailure(
                f"{rep.name}: readyz {st} (state="
                f"{body.get('state', '?')})")
        scrape = None
        try:
            if self.chaos is not None and self.chaos.scrape_fails(rep.name):
                raise ReplicaFailure(f"{rep.name}: injected scrape failure")
            mst, text = _get_text(rep.host, rep.port, "/metrics", t)
            if mst == 200:
                prom = parse_prometheus(text)
                scrape = {
                    "queue_depth": prom.get("picotron_queue_depth", 0.0),
                    "active_slots": prom.get("picotron_active_slots", 0.0),
                    "pool_utilization": prom.get(
                        "picotron_kv_pool_utilization", 0.0),
                    "ttft_p95": hist_quantile(
                        prom, "picotron_ttft_seconds", 0.95),
                    # per-tenant load (empty on tenancy-less replicas):
                    # placement adds the REQUESTING tenant's TTFT p95 on
                    # each candidate, steering an SLO tenant away from
                    # the replica that is slow for IT specifically
                    "tenants": tenant_scrape(prom),
                }
        except ReplicaFailure:
            scrape = None
        self._probe_ok(rep, ready=st == 200, draining=draining,
                       scrape=scrape, role=role)

    def _transition(self, rep: Replica, to: str) -> None:
        """Count + log one breaker transition. Called WITH ``rep._mu``
        held: the counter's own leaf lock nests strictly inside it (one
        direction only — no cycle)."""
        self.registry.counter(
            "picotron_router_breaker_transitions_total",
            "circuit-breaker state changes", replica=rep.name, to=to).inc()

    def _probe_ok(self, rep: Replica, ready: bool, draining: bool,
                  scrape: Optional[dict], role: str = "both") -> None:
        now = self._clock()
        opened_to = None
        with rep._mu:
            rep.ready = ready
            rep.draining = draining
            rep.role = role
            if scrape is not None:
                rep.scrape = scrape
                rep.scrape_t = now
            rep.fails = 0
            rep.okays += 1
            if rep.breaker == "open":
                rep.breaker = "half_open"
                rep.okays = 1
                self._transition(rep, "half_open")
                opened_to = "half_open"
            elif (rep.breaker == "half_open"
                  and rep.okays >= self.cfg.breaker_failures):
                # traffic-free recovery: enough consecutive clean probes
                # close the breaker without risking a trial request
                rep.breaker = "closed"
                self._transition(rep, "closed")
                opened_to = "closed"
        if opened_to:
            self._event("breaker", replica=rep.name, to=opened_to,
                        via="probe")

    def _probe_fail(self, rep: Replica, why: str) -> bool:
        """Record one hard probe failure; returns True when the breaker is
        now open (the caller switches to the reprobe ladder)."""
        opened = False
        with rep._mu:
            rep.ready = False
            rep.okays = 0
            rep.fails += 1
            if (rep.breaker == "half_open"
                    or (rep.breaker == "closed"
                        and rep.fails >= self.cfg.breaker_failures)):
                rep.breaker = "open"
                self._transition(rep, "open")
                opened = True
            is_open = rep.breaker == "open"
        self._event("probe_failure", replica=rep.name, why=why,
                    breaker_opened=opened)
        return is_open

    def _reprobe_open(self, rep: Replica) -> None:
        """Open-state reprobe ladder: ``resilience.retry`` drives
        exponentially backed-off probes (first delay
        ``breaker_backoff_s``, doubling, jittered); the first success
        lands in ``_probe_ok`` which flips half-open. An exhausted ladder
        parks at the cap and starts over — an open replica is reprobed
        forever, just never faster than the cap."""
        def capped_sleep(d: float) -> None:
            # retry()'s raw exponential has no cap of its own: clamp
            # every inter-reprobe delay at the configured ceiling
            self._sleep(min(d, self.cfg.breaker_backoff_max_s), rep)

        while not self._stop.is_set() and not rep.gone.is_set():
            try:
                retry(lambda: self._probe_once(rep),
                      attempts=self.cfg.breaker_probe_attempts,
                      backoff=self.cfg.breaker_backoff_s,
                      jitter=0.25, retry_on=(ReplicaFailure,),
                      desc=f"router-reprobe-{rep.name}",
                      sleep=capped_sleep)
                return
            except ReplicaFailure:
                self._sleep(self.cfg.breaker_backoff_max_s, rep)

    def _request_success(self, rep: Replica) -> None:
        closed = False
        with rep._mu:
            rep.inflight -= 1
            if rep.breaker == "half_open" and rep.trial:
                rep.breaker = "closed"
                rep.fails = 0
                self._transition(rep, "closed")
                closed = True
            rep.trial = False
        if closed:
            self._event("breaker", replica=rep.name, to="closed",
                        via="trial_request")

    def _request_failure(self, rep: Replica, why: str) -> None:
        opened = False
        with rep._mu:
            rep.inflight -= 1
            rep.fails += 1
            rep.okays = 0
            if (rep.breaker == "half_open"
                    or (rep.breaker == "closed"
                        and rep.fails >= self.cfg.breaker_failures)):
                if rep.breaker != "open":
                    rep.breaker = "open"
                    self._transition(rep, "open")
                    opened = True
            rep.trial = False
        self._event("request_failure", replica=rep.name, why=why,
                    breaker_opened=opened)

    def _request_refused(self, rep: Replica) -> None:
        """A shed/drain refusal: the replica is alive (that WAS its
        answer) — no breaker action, just release the slot."""
        with rep._mu:
            rep.inflight -= 1
            rep.trial = False

    # ---- placement --------------------------------------------------------

    def _load(self, rep: Replica, tenant: str = "") -> float:
        """Load score under ``rep._mu`` (caller holds it): scraped queue
        depth + the router's own in-flight placements (fresher than any
        scrape), active slots, pool occupancy, TTFT p95 — plus, for a
        named tenant, THAT tenant's scraped TTFT p95 on this replica
        (picotron_tenant_ttft_seconds): fleet-wide health can hide one
        replica serving one tenant badly (its adapter contending with a
        heavy co-tenant), and the per-tenant term is what routes around
        it."""
        c = self.cfg
        s = rep.scrape
        load = (c.load_queue_weight * (s.get("queue_depth", 0.0)
                                       + rep.inflight)
                + c.load_slot_weight * s.get("active_slots", 0.0)
                + c.load_pool_weight * s.get("pool_utilization", 0.0)
                + c.load_ttft_weight * s.get("ttft_p95", 0.0))
        if tenant:
            ts = s.get("tenants", {}).get(tenant)
            if ts:
                load += c.load_ttft_weight * ts.get("ttft_p95", 0.0)
        return load

    def _candidates(self, excluded=(), kind: str = "decode",
                    tenant: str = "") -> list:
        """[(replica, load)] of currently placeable replicas for ``kind``
        of work: "decode" (the /generate path — prefill-only replicas are
        NOT candidates, they would otherwise score as idle decode
        targets) or "prefill" (the /kv/export handoff — dedicated
        prefill workers only; a fleet without any simply serves
        colocated)."""
        now = self._clock()
        out = []
        for rep in self.replicas.values():
            if rep.name in excluded:
                continue
            with rep._mu:
                if kind == "decode" and rep.role == "prefill":
                    continue
                if kind == "prefill" and rep.role != "prefill":
                    continue
                if rep.breaker == "open":
                    continue
                if rep.breaker == "half_open" and rep.trial:
                    continue  # one trial at a time through a half-open door
                if not rep.ready or rep.draining:
                    continue
                if now - rep.scrape_t > self.cfg.scrape_stale_s:
                    continue  # unknown load is unplaceable load
                out.append((rep, self._load(rep, tenant)))
        return out

    def _eligible(self) -> list:
        return [rep for rep, _ in self._candidates()]

    def _affinity_owner(self, prompt,
                        tenant: str = "") -> Optional[Replica]:
        """The rendezvous-top decode candidate for ``prompt``'s prefix
        key (load ignored): the replica whose radix cache accumulates
        this prefix under affinity placement — the cross-replica lookup's
        source of truth. None for page-less prompts or an empty set."""
        key = prefix_key(prompt, self.cfg.affinity_page_len, tenant)
        if key is None:
            return None
        cands = self._candidates()
        if not cands:
            return None
        return max((rep for rep, _ in cands),
                   key=lambda rep: _rendezvous(key, rep.name))

    def place(self, prompt, excluded=(), kind: str = "decode",
              tenant: str = "") -> Optional[Replica]:
        """Pick a replica for ``prompt`` (None when nothing is eligible):
        the rendezvous affinity pick while it is within
        ``affinity_load_slack`` of the least-loaded candidate, else
        least-loaded. Reserves an inflight slot (and the half-open trial
        token) on the pick."""
        cands = self._candidates(excluded, kind=kind, tenant=tenant)
        key = prefix_key(prompt, self.cfg.affinity_page_len, tenant)
        while cands:
            best = min(load for _, load in cands)
            pick = None
            if key is not None:
                for rep, load in sorted(
                        cands, key=lambda c: _rendezvous(key, c[0].name),
                        reverse=True):
                    if load <= best + self.cfg.affinity_load_slack:
                        pick = rep
                        break
            if pick is None:
                pick = min(cands, key=lambda c: c[1])[0]
            with pick._mu:
                if pick.breaker == "half_open" and pick.trial:
                    # lost the race for the one half-open trial token
                    # (_candidates read it before another placement took
                    # it): fall through to the next candidate
                    reserved = False
                else:
                    pick.inflight += 1
                    if pick.breaker == "half_open":
                        pick.trial = True
                    reserved = True
            if not reserved:
                cands = [c for c in cands if c[0] is not pick]
                continue
            self.registry.counter(
                "picotron_router_placements_total",
                "requests placed, by replica", replica=pick.name).inc()
            return pick
        return None

    # ---- disaggregation: handoff export + cross-replica prefix fetch ------

    def _export_handoff(self, spec: dict, rid: str, prompt: list,
                        tracer, root) -> Optional[dict]:
        """Run the prompt's prefill at its affinity PREFILL worker and
        return the KV transport payload (POST /kv/export), or None — no
        prefill workers, all refused, or every attempt failed — in which
        case the caller falls back to self-prefill at the decode
        placement (nothing was streamed to the client, so this is the
        replay bookkeeping's zero-delivered path). Export failures feed
        the breaker exactly like request failures; sheds are graceful."""
        tried: set = set()
        tenant = str(spec.get("tenant") or "")
        for _ in range(self.cfg.place_attempts):
            rep = self.place(prompt, excluded=tried, kind="prefill",
                             tenant=tenant)
            if rep is None:
                break
            sub = {"prompt": prompt, "request_id": rid,
                   "uid": f"{rid}.pf{len(tried) + 1}"}
            for k in ("temperature", "top_k", "top_p", "eos_id",
                      "timeout_s", "tenant"):
                if k in spec:
                    sub[k] = spec[k]
            span = tracer.begin("handoff", parent=root, request_id=rid,
                                replica=rep.name)
            t0 = self._clock()
            try:
                if self.chaos is not None:
                    self.chaos.on_export(rep.name)
                st, body = _post_json(
                    rep.host, rep.port, "/kv/export", sub,
                    self.cfg.handoff_timeout_s,
                    on_read=(None if self.chaos is None else
                             lambda: self.chaos.on_export_read(rep.name)))
                if st in (429, 503):
                    self._request_refused(rep)
                    tried.add(rep.name)
                    tracer.end(span, outcome="refused")
                    continue
                if st == 400:
                    # the CLIENT's bad request, not the replica's fault
                    # (the same discipline as _attempt's client_error):
                    # no breaker feedback — fall back so the decode
                    # placement's /generate returns the client-visible
                    # 400 through the normal path
                    self._request_refused(rep)
                    tracer.end(span, outcome="client_error")
                    return None
                if st != 200 or not isinstance(body.get("kv"), dict):
                    raise ReplicaFailure(
                        f"{rep.name}: POST /kv/export {st}")
                payload = body["kv"]
                self._request_success(rep)
                dt = self._clock() - t0
                self._handoff_hist.observe(dt)
                self._handoff_bytes.inc(int(payload.get("bytes_total", 0)))
                with self._ctr_mu:
                    self._handoffs["served"] += 1
                tracer.end(span, outcome="served",
                           tokens=len(payload.get("token_ids", ())),
                           bytes=int(payload.get("bytes_total", 0)))
                return payload
            except ReplicaFailure as e:
                # prefill-worker death (or a severed page stream)
                # mid-handoff: breaker feedback, then the next prefill
                # worker — or the caller's re-prefill fallback
                self._request_failure(rep, str(e))
                tried.add(rep.name)
                tracer.end(span, outcome="failed", error=str(e)[:200])
                self._event("handoff_failed", request_id=rid,
                            replica=rep.name, why=str(e))
                continue
        # prefill workers exist in the fleet but none produced a payload
        # (refused, failed, breaker-open, draining): the decode placement
        # self-prefills — the degradation signal an operator watches.
        # A fleet with NO prefill-role replicas is colocated by design,
        # not degraded, and counts nothing.
        has_prefill = False
        for rep in self.replicas.values():
            with rep._mu:
                if rep.role == "prefill":
                    has_prefill = True
                    break
        if tried or has_prefill:
            with self._ctr_mu:
                self._handoffs["fallback"] += 1
        return None

    def _prefix_fetch(self, owner: Replica, rep: Replica,
                      prompt: list, tenant: str = "") -> None:
        """Cross-replica prefix-cache lookup: pull ``owner``'s longest
        cached page-aligned prefix of ``prompt`` and import it at
        ``rep`` — a placement that escaped its affinity owner still
        reuses the cluster's one prefill of the shared prefix. SOFT end
        to end: every failure is counted and skipped, never a breaker
        verdict or a client error (the worst case is the prefill the
        escape would have paid anyway)."""
        outcome = "error"
        try:
            lookup = {"ids": prompt}
            if tenant:
                # scope the lookup to the tenant's radix domain — a
                # lookup must never vouch pages across the isolation
                # boundary (the payload itself carries the tenant, so
                # the import lands in the right domain at ``rep``)
                lookup["tenant"] = tenant
            st, body = _post_json(owner.host, owner.port, "/kv/pages",
                                  lookup, self.cfg.probe_timeout_s)
            if st != 200 or body.get("matched", 0) \
                    < self.cfg.affinity_page_len:
                outcome = "miss"
                return
            st, _ = _post_json(rep.host, rep.port, "/kv/import",
                               {"kv": body["kv"]},
                               self.cfg.handoff_timeout_s)
            if st == 200:
                outcome = "hit"
                self._handoff_bytes.inc(
                    int(body["kv"].get("bytes_total", 0)))
        except ReplicaFailure:
            pass
        finally:
            with self._ctr_mu:
                self._prefix_fetches[outcome] += 1

    # ---- request path -----------------------------------------------------

    def route(self, spec: dict, rid: str, on_token=None) -> dict:
        """Serve one request against the fleet, failing over mid-stream as
        needed. ``on_token(tok)`` fires once per delivered token (the
        streaming splice); returns the terminal payload
        ``{request_id, tokens, finish_reason, replays, attempts,
        replica}``. Raises RouteRefused when nothing was streamed and no
        replica served (the caller maps it to 400/503)."""
        prompt = spec.get("prompt")
        if (not isinstance(prompt, list) or not prompt
                or not all(isinstance(t, int) for t in prompt)):
            raise RouteRefused(
                400, "prompt must be a non-empty list of token ids")
        try:
            max_new = int(spec.get("max_new_tokens", 32))
        except (TypeError, ValueError) as e:
            raise RouteRefused(400, f"bad max_new_tokens: {e}") from e
        if max_new < 1:
            raise RouteRefused(400, "max_new_tokens must be >= 1")
        tenant = spec.get("tenant")
        if tenant is not None and not isinstance(tenant, str):
            raise RouteRefused(400, "tenant must be a string")
        tenant = tenant or ""
        t0 = self._clock()
        tracer = self.obs.tracer
        root = tracer.begin("route", request_id=rid)
        delivered: list = []
        excluded: set = set()
        replays = 0
        refusals = 0
        attempt = 0
        finish = None
        last_replica = None
        state = "failed"
        prefix_fetched = False
        try:
            # disaggregated prefill: hand the prompt to its affinity
            # prefill worker FIRST — the decode placement then seats the
            # returned pages instead of burning dispatch rounds on the
            # prefill (None = no prefill workers / export failed: the
            # decode placement self-prefills, nothing client-visible)
            kv_payload = None
            if self.cfg.disagg:
                kv_payload = self._export_handoff(spec, rid, prompt,
                                                  tracer, root)
            while True:
                if delivered:
                    # failover landed exactly on a finished generation:
                    # synthesize the terminal the dead replica owed us
                    eos = spec.get("eos_id")
                    if eos is not None and delivered[-1] == int(eos):
                        finish = "eos"
                        break
                    if len(delivered) >= max_new:
                        finish = "length"
                        break
                rep = self.place(prompt + delivered, excluded,
                                 tenant=tenant)
                if rep is None:
                    if delivered:
                        finish = "error"  # mid-stream with no survivor
                        break
                    raise RouteRefused(
                        503, "no replica eligible",
                        self.cfg.retry_after_s)
                attempt += 1
                last_replica = rep.name
                if (kv_payload is None and not delivered and not
                        prefix_fetched and self.cfg.prefix_fetch):
                    # no handoff payload to seat: if the placement escaped
                    # its affinity owner, pull the owner's cached prefix
                    # so the shared prefix still prefills once per cluster
                    prefix_fetched = True
                    owner = self._affinity_owner(prompt, tenant)
                    if owner is not None and owner.name != rep.name:
                        self._prefix_fetch(owner, rep, prompt, tenant)
                try:
                    outcome, detail = self._attempt(
                        rep, spec, rid, attempt, prompt, delivered,
                        max_new, on_token, root, tracer,
                        kv_payload=kv_payload)
                except BaseException:
                    # a non-replica abort (the CLIENT dropped its
                    # connection mid-splice): release the placement slot
                    # without a breaker verdict — the replica did nothing
                    # wrong
                    self._request_refused(rep)
                    raise
                if outcome == "served":
                    self._request_success(rep)
                    finish = detail
                    break
                if outcome == "refused":
                    self._request_refused(rep)
                    self._placement_retries.inc()
                    excluded.add(rep.name)
                    refusals += 1
                    if refusals >= self.cfg.place_attempts:
                        if delivered:
                            finish = "error"
                            break
                        raise RouteRefused(
                            503,
                            f"every placement refused ({detail})",
                            self.cfg.retry_after_s)
                    continue
                if outcome == "client_error":
                    self._request_refused(rep)
                    if delivered:
                        # a replay the fleet can no longer express (e.g.
                        # the replayed prompt+delivered fills the
                        # replica's window): the client keeps every
                        # delivered token and gets a terminal — never a
                        # torn stream, never a 400 that eats partials
                        finish = "error"
                        break
                    raise RouteRefused(400, detail)
                # hard failure: breaker feedback, then replay (tokens
                # were delivered) or placement retry (none were)
                self._request_failure(rep, detail)
                excluded.add(rep.name)
                if delivered:
                    replays += 1
                    if replays > self.cfg.replay_budget:
                        finish = "error"
                        break
                    self._replays.inc()
                    tracer.record("replay", self._clock(), self._clock(),
                                  parent=root, request_id=rid,
                                  from_replica=rep.name,
                                  delivered=len(delivered), why=detail)
                    self._event("replay", request_id=rid,
                                from_replica=rep.name,
                                delivered=len(delivered), why=detail)
                else:
                    self._placement_retries.inc()
                    refusals += 1
                    if refusals >= self.cfg.place_attempts:
                        raise RouteRefused(
                            503, f"every placement failed ({detail})",
                            self.cfg.retry_after_s)
            state = "completed" if finish in ("eos", "length", "timeout") \
                else "failed"
            return {"request_id": rid, "tokens": list(delivered),
                    "finish_reason": finish, "replays": replays,
                    "attempts": attempt, "replica": last_replica}
        except RouteRefused as e:
            state = "client_error" if e.status == 400 else "shed"
            raise
        except BaseException:
            # a non-replica abort (the client dropped its connection):
            # its own ledger state — "failed" is reserved for requests
            # the FLEET could not finish, the signal operators page on
            state = "abandoned"
            raise
        finally:
            with self._ctr_mu:
                self.requests[state] += 1
            self._route_hist.observe(self._clock() - t0)
            tracer.end(root, finish_reason=finish or "refused",
                       tokens=len(delivered), replays=replays,
                       state=state)
            self._event("request", request_id=rid, state=state,
                        finish_reason=finish, tokens=len(delivered),
                        replays=replays, attempts=attempt,
                        replica=last_replica)

    def _attempt(self, rep: Replica, spec: dict, rid: str, n: int,
                 prompt: list, delivered: list, max_new: int,
                 on_token, root, tracer, kv_payload=None) -> tuple:
        """One placement attempt: stream ``/generate`` from ``rep``,
        appending tokens to ``delivered`` as they arrive. Returns
        ``(outcome, detail)`` with outcome one of ``served`` (detail =
        finish_reason), ``refused`` (shed — nothing streamed), ``failed``
        (hard failure; ``delivered`` may have grown), ``client_error``.

        ``kv_payload`` is the disaggregated handoff: on the first
        attempt (nothing delivered) the replica seats it — first token
        included — with zero prefill dispatches; on a replay the payload
        rides along WITHOUT its first token as a prefix hint, so the
        survivor radix-hits the prompt and prefills only the delivered
        continuation (bit-identical greedy either way)."""
        sub = {"prompt": prompt + delivered,
               "max_new_tokens": max_new - len(delivered),
               "stream": True, "uid": f"{rid}.a{n}", "request_id": rid}
        for k in ("temperature", "top_k", "top_p", "eos_id", "timeout_s",
                  "tenant"):
            if k in spec:
                sub[k] = spec[k]
        if kv_payload is not None:
            kv = dict(kv_payload)
            if delivered:
                # the first token was already delivered: the payload now
                # vouches for pages only, never a token
                kv.pop("first_token", None)
            sub["kv"] = kv
        span = tracer.begin("attempt", parent=root, request_id=rid,
                            replica=rep.name, n=n)
        got = 0
        conn = None
        try:
            try:
                conn = http.client.HTTPConnection(
                    rep.host, rep.port,
                    timeout=self.cfg.connect_timeout_s)
                conn.request("POST", "/generate", json.dumps(sub),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                if resp.status in (429, 503):
                    body = json.loads(resp.read() or b"{}")
                    return ("refused",
                            f"{resp.status}: {body.get('error', 'shed')}")
                if resp.status == 400:
                    body = json.loads(resp.read() or b"{}")
                    return ("client_error",
                            body.get("error", "bad request"))
                if resp.status != 200:
                    raise ReplicaFailure(
                        f"{rep.name}: POST /generate {resp.status}")
                if conn.sock is not None:
                    # connect deadline served its purpose; from here the
                    # idle timeout bounds a silently wedged stream
                    conn.sock.settimeout(self.cfg.stream_idle_timeout_s)
                while True:
                    line = resp.readline()
                    if not line:
                        raise ReplicaFailure(
                            f"{rep.name}: stream ended without done")
                    if self.chaos is not None:
                        self.chaos.on_stream_row(rep.name, got)
                    row = json.loads(line)
                    ev = row.get("event")
                    if ev == "token":
                        if row.get("request_id", rid) != rid:
                            # a foreign row can only mean a replica-side
                            # routing bug: drop it, keep the count visible
                            self.registry.counter(
                                "picotron_router_row_mismatch_total",
                                "stream rows whose request_id was not "
                                "ours").inc()
                            continue
                        tok = int(row["token"])
                        delivered.append(tok)
                        got += 1
                        if on_token is not None:
                            on_token(tok)
                        continue
                    if ev == "done":
                        fr = row.get("finish_reason")
                        if fr == "error":
                            raise ReplicaFailure(
                                f"{rep.name}: replica finished 'error'")
                        if fr == "shed":
                            if got:
                                raise ReplicaFailure(
                                    f"{rep.name}: shed after streaming "
                                    f"{got} tokens")
                            return ("refused", "shed at drain")
                        if fr not in ("eos", "length", "timeout"):
                            raise ReplicaFailure(
                                f"{rep.name}: unknown finish_reason "
                                f"{fr!r}")
                        return ("served", fr)
            except _TRANSPORT_ERRORS as e:
                # connection drop, torn NDJSON row, idle timeout: the
                # mid-stream death the replay path exists for
                raise ReplicaFailure(
                    f"{rep.name}: {type(e).__name__}: {e}") from e
        except ReplicaFailure as e:
            return ("failed", str(e))
        finally:
            if conn is not None:
                conn.close()
            tracer.end(span, tokens=got)

    # ---- observability ----------------------------------------------------

    def stats(self) -> dict:
        now = self._clock()
        reps = {name: rep.snapshot(now)
                for name, rep in self.replicas.items()}
        eligible = [rep.name for rep in self._eligible()]
        self.registry.gauge(
            "picotron_router_replicas_eligible",
            "replicas currently placeable").set(len(eligible))
        with self._ctr_mu:
            requests = dict(self.requests)
            handoffs = dict(self._handoffs)
            prefix_fetches = dict(self._prefix_fetches)
        return {
            "replicas": reps,
            "eligible": eligible,
            "requests": requests,
            "replays": int(self._replays.value),
            "placement_retries": int(self._placement_retries.value),
            "route_s": self._route_hist.percentiles(),
            "handoffs": handoffs,
            "handoff_bytes": int(self._handoff_bytes.value),
            "handoff_s": self._handoff_hist.percentiles(),
            "prefix_fetches": prefix_fetches,
            "uptime_s": round(now - self._start_t, 3),
        }

    def metrics_text(self) -> str:
        self.stats()  # refresh the eligibility gauge for scrapers
        return self.registry.prometheus() + GLOBAL_REGISTRY.prometheus()


# --------------------------------------------------------------------------- #
# HTTP surface (mirrors tools/serve.py)
# --------------------------------------------------------------------------- #

MAX_BODY_BYTES = 8 << 20


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.0"  # close-delimited NDJSON streaming

    @property
    def router(self) -> Router:
        return self.server.router

    def log_message(self, *a):  # the router's JSON lines replace these
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
        r = self.router
        if self.path == "/healthz":
            self._json(200, {"ok": True})
        elif self.path == "/readyz":
            n = len(r._eligible())
            self._json(200 if n else 503,
                       {"ok": n > 0, "eligible_replicas": n})
        elif self.path == "/statz":
            self._json(200, r.stats())
        elif self.path == "/metrics":
            body = r.metrics_text().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        elif self.path == "/tracez":
            self._json(200, r.obs.tracer.chrome_trace())
        elif self.path == "/replicas":
            now = r._clock()
            self._json(200, {name: rep.snapshot(now)
                             for name, rep in sorted(r.replicas.items())})
        else:
            self._json(404, {"error": f"unknown path {self.path}"})

    def do_POST(self) -> None:
        if self.path not in ("/generate", "/replicas"):
            self._json(404, {"error": f"unknown path {self.path}"})
            return
        try:
            n = int(self.headers.get("Content-Length", 0))
        except ValueError as e:
            self._json(400, {"error": f"bad Content-Length: {e}"})
            return
        if n < 0 or n > MAX_BODY_BYTES:
            self._json(400 if n < 0 else 413,
                       {"error": f"bad body length {n}"})
            return
        try:
            spec = json.loads(self.rfile.read(n) or b"{}")
        except (ValueError, json.JSONDecodeError) as e:
            self._json(400, {"error": f"bad request body: {e}"})
            return
        if not isinstance(spec, dict):
            self._json(400, {"error": "request body must be a JSON object"})
            return
        if self.path == "/replicas":
            self._add_replica(spec)
            return
        r = self.router
        rid = str(spec.get("request_id") or r._next_rid())
        if spec.get("stream"):
            self._stream(spec, rid)
        else:
            try:
                payload = r.route(spec, rid)
            except RouteRefused as e:
                headers = ([("Retry-After", str(e.retry_after))]
                           if e.retry_after else [])
                self._json(e.status,
                           {"error": e.reason, "request_id": rid,
                            "shed": e.status != 400}, headers)
                return
            status = 500 if payload["finish_reason"] == "error" else 200
            self._json(status, payload)

    def _add_replica(self, spec: dict) -> None:
        """POST /replicas — the fleet controller's registration surface.
        Body: {"replica": "host:port"} or {"replica": {"name", "host",
        "port"}}. 200 with the new snapshot, 409 on a duplicate name,
        400 on a malformed spec."""
        raw = spec.get("replica")
        if isinstance(raw, dict):
            try:
                raw = (raw.get("name") or f"{raw['host']}:{raw['port']}",
                       raw["host"], raw["port"])
            except KeyError as e:
                self._json(400, {"error": f"replica spec missing {e}"})
                return
        try:
            rep = self.router.add_replica(raw)
        except DuplicateReplica as e:
            self._json(409, {"error": str(e)})
            return
        except (ValueError, TypeError) as e:
            self._json(400, {"error": f"bad replica spec: {e}"})
            return
        self._json(200, {"ok": True, "replica": rep.name,
                         **rep.snapshot(self.router._clock())})

    def do_DELETE(self) -> None:
        if not self.path.startswith("/replicas/"):
            self._json(404, {"error": f"unknown path {self.path}"})
            return
        name = unquote(self.path[len("/replicas/"):])
        try:
            snap = self.router.remove_replica(name)
        except KeyError:
            self._json(404, {"error": f"unknown replica {name!r}"})
            return
        self._json(200, {"ok": True, "replica": name, **snap})

    def _stream(self, spec: dict, rid: str) -> None:
        """NDJSON splice: the header is deferred until the route either
        delivers a first token or refuses outright, so a full-fleet
        outage is still a clean 503 + Retry-After instead of a 200 that
        dies."""
        started = threading.Event()

        def emit(obj) -> None:
            self.wfile.write((json.dumps(obj) + "\n").encode())
            self.wfile.flush()

        def on_token(tok: int) -> None:
            if not started.is_set():
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.end_headers()
                started.set()
            try:
                emit({"event": "token", "request_id": rid, "token": tok})
            except (BrokenPipeError, ConnectionResetError):
                # the CLIENT went away: abort the route (counted
                # "abandoned"; the replica finishes the in-flight
                # generation under its own timeout contract)
                started.set()
                raise _ClientGone()

        try:
            try:
                payload = self.router.route(spec, rid, on_token=on_token)
            except RouteRefused as e:
                if not started.is_set():
                    headers = ([("Retry-After", str(e.retry_after))]
                               if e.retry_after else [])
                    self._json(e.status,
                               {"error": e.reason, "request_id": rid,
                                "shed": e.status != 400}, headers)
                return
            if not started.is_set():
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.end_headers()
                started.set()
            emit({"event": "done", **payload})
        except _ClientGone:
            pass
        except (BrokenPipeError, ConnectionResetError):
            pass


class _ClientGone(Exception):
    """The downstream client dropped its connection mid-stream."""


class RouterServer:
    """Router + ThreadingHTTPServer on background threads — the embedding
    entry point for the CLI, the smoke drive, and the tests."""

    def __init__(self, replicas, cfg: Optional[RouterConfig] = None, *,
                 host: str = "127.0.0.1", port: int = 0, **router_kw):
        self.router = Router(replicas, cfg, **router_kw)
        self.httpd = ThreadingHTTPServer((host, port), _Handler)
        self.httpd.router = self.router
        self.port = self.httpd.server_address[1]
        self._http_thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self.router.start()
        self._http_thread = threading.Thread(
            target=self.httpd.serve_forever, name="router-http",
            daemon=True)
        self._http_thread.start()

    def stop(self) -> None:
        self.router.stop()
        self.httpd.shutdown()
        if self._http_thread is not None:
            self._http_thread.join(timeout=10)
        self.httpd.server_close()


# --------------------------------------------------------------------------- #
# smoke drive (`make router-chaos-smoke`) + CLI
# --------------------------------------------------------------------------- #


def _stream_post(port: int, spec: dict, on_token=None,
                 host: str = "127.0.0.1", timeout: float = 300.0):
    """Incremental NDJSON client: POSTs with stream=True, fires
    ``on_token(i, row)`` per token row as it ARRIVES (the hook the chaos
    drills key their kill timing off), returns (status, [rows])."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/generate", json.dumps({**spec, "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            return resp.status, [json.loads(resp.read() or b"{}")]
        rows = []
        i = 0
        while True:
            line = resp.readline()
            if not line:
                return resp.status, rows
            row = json.loads(line)
            rows.append(row)
            if row.get("event") == "token":
                if on_token is not None:
                    on_token(i, row)
                i += 1
            if row.get("event") == "done":
                return resp.status, rows
    finally:
        conn.close()


def _wait_for(cond, timeout: float = 20.0, poll: float = 0.02) -> bool:
    """Poll ``cond()`` until true (True) or the deadline passes (False)."""
    deadline = time.monotonic() + timeout
    while True:
        if cond():
            return True
        if time.monotonic() >= deadline:
            return False
        time.sleep(poll)


def _breaker(router: Router, name: str) -> str:
    rep = router.replicas[name]
    with rep._mu:
        return rep.breaker


def _smoke_fleet(n: int, roles=None):
    """n in-process serve.py replicas over IDENTICAL tiny random-init
    models (same seed -> same params -> greedy outputs are a shared
    bit-exact oracle), streaming per token (decode_block_len 1).
    ``roles`` (e.g. ``("prefill", "decode")``) builds a disaggregated
    fleet on the paged layout — the KV-page transport's requirement."""
    import jax

    from picotron_tpu.config import Config
    from picotron_tpu.inference import InferenceEngine
    from picotron_tpu.models import llama
    from picotron_tpu.tools import serve
    from picotron_tpu.tools.generate import SMOKE_CONFIG
    from picotron_tpu.train import _ensure_devices

    servers = []
    cfg0 = Config.from_dict(SMOKE_CONFIG)
    jit_init = jax.jit(lambda k: llama.init_params(k, cfg0.model))
    for i in range(n):
        cfg = Config.from_dict(SMOKE_CONFIG)
        cfg.inference.decode_block_len = 1
        if roles is not None:
            cfg.inference.role = roles[i]
            cfg.inference.kv_layout = "paged"
            cfg.inference.kv_page_len = 8
        _ensure_devices(cfg)
        engine = InferenceEngine(cfg, slots=2, max_seq_len=64)
        params = engine.shard_params(jit_init(jax.random.PRNGKey(0)))
        srv = serve.Server(engine, params, port=0,
                           log=lambda *a, **k: None)
        srv.start()
        servers.append(srv)
    return servers


def _smoke_disagg(check) -> None:
    """The disaggregation rungs of `make router-chaos-smoke` (ISSUE 15):
    a prefill + decode two-role fleet behind a fresh router — the happy
    handoff (decode worker seats pages, zero prefill dispatches), then
    the chaos pair: sever the page stream mid-transfer and kill the
    prefill worker mid-export. In every case the client gets every token
    exactly once, greedy bit-identical to the decode worker's own
    self-prefilled run."""
    from picotron_tpu.resilience.chaos import RouterChaos
    from picotron_tpu.tools import serve

    servers = _smoke_fleet(2, roles=("prefill", "decode"))
    pre, dec = servers
    names = [f"127.0.0.1:{s.port}" for s in servers]
    chaos = RouterChaos()
    cfg = RouterConfig(
        probe_interval_s=0.05, probe_timeout_s=2.0, breaker_failures=3,
        breaker_backoff_s=0.05, breaker_backoff_max_s=0.4,
        scrape_stale_s=2.0, connect_timeout_s=5.0)
    rs = RouterServer(names, cfg, chaos=chaos, log=lambda *a, **k: None)
    rs.start()
    router = rs.router
    try:
        check("disagg_fleet_eligible", _wait_for(
            lambda: len(router._candidates(kind="prefill")) == 1
            and len(router._eligible()) == 1, timeout=30))
        check("disagg_roles_probed",
              router.replicas[names[0]].snapshot(0)["role"] == "prefill"
              and router.replicas[names[1]].snapshot(0)["role"] == "decode")

        def run(prompt, rid):
            st, rows = _stream_post(
                rs.port, {"prompt": prompt, "max_new_tokens": 12,
                          "request_id": rid})
            toks = [r["token"] for r in rows if r.get("event") == "token"]
            done = [r for r in rows if r.get("event") == "done"]
            ok = (st == 200 and len(done) == 1
                  and done[0]["finish_reason"] == "length"
                  and done[0]["tokens"] == toks and len(toks) == 12)
            return ok, toks

        def oracle(prompt):
            # the decode worker self-prefills a direct request: the
            # greedy oracle for the same prompt (prefix sharing is
            # output-invariant — pinned in tests/test_paged_kv.py)
            st, body = serve._post(dec.port, {"prompt": prompt,
                                              "max_new_tokens": 12})
            return st == 200 and body["finish_reason"] == "length", \
                body.get("tokens")

        # happy handoff: prefill worker exports, decode worker seats
        p1 = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3, 2, 3]
        ok, toks = run(p1, "dg-1")
        stz = serve._get(dec.port, "/statz")[1]
        check("disagg_handoff_served",
              ok and stz["handoff_seated"] == 1
              and stz["prefill_dispatches"] == 0
              and router.stats()["handoffs"]["served"] == 1
              and router.stats()["handoff_bytes"] > 0)
        ook, otoks = oracle(p1)
        check("disagg_bit_identical", ook and otoks == toks)
        pstz = serve._get(pre.port, "/statz")[1]
        check("disagg_prefill_worker_prefilled",
              pstz["admitted"] == 1 and pstz["completed"] == 1)

        # sever the page stream mid-transfer: fallback self-prefill,
        # exactly-once tokens, bit-identical
        chaos.sever_export(names[0])
        p2 = [11, 12, 13, 14, 15, 16, 17, 18, 11, 12, 13, 14, 15, 16,
              17, 18, 19, 20]
        ok, toks = run(p2, "dg-sever")
        ook, otoks = oracle(p2)
        check("disagg_sever_exactly_once",
              ok and ook and otoks == toks
              and router.stats()["handoffs"]["fallback"] >= 1)

        # kill the prefill worker mid-export: same client contract
        chaos.kill_on_export(names[0], pre)
        p3 = [21, 22, 23, 24, 25, 26, 27, 28, 21, 22, 23, 24, 25, 26,
              27, 28, 29, 30]
        ok, toks = run(p3, "dg-kill")
        ook, otoks = oracle(p3)
        check("disagg_kill_mid_export_exactly_once",
              ok and ook and otoks == toks)
    finally:
        rs.stop()
        try:
            dec.drain_and_join(timeout=60)
        except OSError:
            pass


def _smoke() -> int:
    """The `make router-chaos-smoke` drive — the ISSUE 12 acceptance
    drill end to end. Returns an exit code."""
    from picotron_tpu.resilience.chaos import RouterChaos
    from picotron_tpu.tools import serve

    fail: list = []

    def check(name: str, ok) -> None:
        print(f"router-chaos-smoke: {name}: {'ok' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            fail.append(name)

    servers = _smoke_fleet(3)
    ports = [s.port for s in servers]
    names = [f"127.0.0.1:{p}" for p in ports]
    by_name = dict(zip(names, servers))
    chaos = RouterChaos()
    # Room for a loaded machine (six test workers beside this one): with a
    # probe held to 0.4 s and a scrape stale after 1 s, three slow probes
    # of a HEALTHY replica opened its breaker, and the drill then counted a
    # replay, a breaker that was not "closed" or a request too many
    # (12 copies at once: every one failed, on six different checks). The
    # drills that need a dead probe make one (a kill, a flap, a stall of
    # twice the timeout), whatever the timeout is.
    cfg = RouterConfig(
        probe_interval_s=0.05, probe_timeout_s=2.0,
        breaker_failures=3, breaker_backoff_s=0.05,
        breaker_backoff_max_s=0.4, breaker_probe_attempts=4,
        scrape_stale_s=3.0, stream_idle_timeout_s=60.0,
        connect_timeout_s=5.0)
    rs = RouterServer(names, cfg, chaos=chaos, log=lambda *a, **k: None)
    rs.start()
    router = rs.router
    killed: dict = {}
    try:
        check("fleet_eligible", router.wait_eligible(3, timeout=30))
        check("healthz", serve._get(rs.port, "/healthz")[0] == 200)
        check("readyz", serve._get(rs.port, "/readyz")[0] == 200)

        # greedy oracle: one unfaulted single-replica run (all replicas
        # hold identical params, so any one of them is the oracle)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9, 3]
        spec = {"prompt": prompt, "max_new_tokens": 24}
        st, body = serve._post(ports[0], spec)
        oracle = body["tokens"]
        check("oracle", st == 200 and len(oracle) == 24)

        # routed request matches the oracle; request_id echoes end to end
        st, body = serve._post(rs.port, {**spec, "request_id": "smk-1"})
        check("routed_generate", st == 200 and body["tokens"] == oracle
              and body["request_id"] == "smk-1" and body["replays"] == 0)

        # prefix affinity: page-aligned shared prefixes land on ONE replica
        k = prefix_key(prompt, cfg.affinity_page_len)
        want = router.place(prompt)
        router._request_refused(want)  # release the probe placement
        again = router.place(prompt)
        router._request_refused(again)
        check("affinity_stable",
              k is not None and want is not None
              and again.name == want.name)

        # ---- the acceptance drill: SIGKILL (in-process) one replica ----
        # holding an in-flight greedy stream; the spliced client stream
        # must equal the unfaulted oracle bit for bit, with replays == 1.
        def kill_at(i, row) -> None:
            if i == 4 and not killed:
                victim = None
                for nm, rep in router.replicas.items():
                    with rep._mu:
                        busy = rep.inflight > 0
                    if busy:
                        victim = nm
                        break
                killed["name"] = victim or names[0]
                chaos.kill(by_name[killed["name"]])

        st, rows = _stream_post(rs.port, {**spec, "request_id": "smk-kill"},
                                on_token=kill_at)
        toks = [r["token"] for r in rows if r.get("event") == "token"]
        done = [r for r in rows if r.get("event") == "done"]
        check("kill_mid_stream_spliced",
              st == 200 and len(done) == 1 and killed
              and done[0]["finish_reason"] == "length"
              and done[0]["replays"] == 1
              and done[0]["tokens"] == toks)
        check("kill_bit_identical", toks == oracle)
        check("kill_request_id",
              all(r.get("request_id") == "smk-kill" for r in rows))

        # the dead replica's breaker opens once the prober sees it
        check("dead_breaker_open", _wait_for(
            lambda: _breaker(router, killed["name"]) == "open"))

        survivors = [nm for nm in names if nm != killed["name"]]

        # ---- flap + stall drill on one survivor: breaker opens, then ----
        # recovers through half-open, with zero client-visible errors.
        flappy = survivors[0]
        chaos.flap(by_name[flappy], down=True)
        check("flap_breaker_open", _wait_for(
            lambda: _breaker(router, flappy) == "open"))
        st, body = serve._post(rs.port, {**spec, "request_id": "smk-flap"})
        check("flap_requests_survive",
              st == 200 and body["tokens"] == oracle)
        chaos.flap(by_name[flappy], down=False)
        check("flap_recovered_closed", _wait_for(
            lambda: _breaker(router, flappy) == "closed"))

        # stall past the probe timeout: reads as a hard failure ladder
        chaos.stall(by_name[flappy], seconds=cfg.probe_timeout_s * 2)
        check("stall_breaker_open", _wait_for(
            lambda: _breaker(router, flappy) == "open"))
        chaos.unstall(by_name[flappy])
        check("stall_recovered_closed", _wait_for(
            lambda: _breaker(router, flappy) == "closed"))

        # ---- scrape-failure injection: candidate drop WITHOUT a ----
        # breaker trip, recovery once the scrape path heals
        scrapey = survivors[1]

        def scrapey_eligible() -> bool:
            return scrapey in [r.name for r in router._eligible()]

        chaos.fail_scrape(scrapey, on=True)
        check("scrape_stale_drops_candidate",
              _wait_for(lambda: not scrapey_eligible())
              and _breaker(router, scrapey) == "closed")
        st, body = serve._post(rs.port, {**spec, "request_id": "smk-scr"})
        check("scrape_requests_survive",
              st == 200 and body["tokens"] == oracle)
        chaos.fail_scrape(scrapey, on=False)
        check("scrape_recovers", _wait_for(scrapey_eligible))

        # ---- drain drill: DURING the drain window (an in-flight ----
        # request still finishing) the prober reads "draining" as
        # graceful — candidate drop, breaker untouched. Once the drain
        # completes the listener closes like the process exited, so the
        # window needs a slow request holding it open.
        slow: dict = {}

        def bg() -> None:
            slow["resp"] = serve._post(
                by_name[flappy].port,
                {"prompt": [9, 8, 7], "max_new_tokens": 48})

        t = threading.Thread(target=bg)
        t.start()
        _wait_for(lambda: serve._get(by_name[flappy].port,
                                     "/statz")[1].get("active_slots", 0)
                  > 0, timeout=60)
        by_name[flappy].front.begin_drain()
        _wait_for(lambda: router.replicas[flappy].snapshot(
            time.monotonic())["draining"])
        snap = router.replicas[flappy].snapshot(time.monotonic())
        check("drain_graceful",
              snap["draining"] and snap["breaker"] == "closed"
              and flappy not in [r.name for r in router._eligible()])
        t.join(120)
        check("drain_inflight_served",
              slow.get("resp", (0, {}))[0] == 200)
        st, body = serve._post(rs.port, {**spec, "request_id": "smk-end"})
        check("post_drain_served",
              st == 200 and body["tokens"] == oracle)

        # ---- accounting: the router's own registry holds the story ----
        mst, mtext = serve._get_text(rs.port, "/metrics")
        prom = parse_prometheus(mtext)
        stats = router.stats()
        check("metrics_accounting",
              mst == 200
              and prom.get("picotron_router_replays_total") == 1
              and prom.get(
                  'picotron_router_requests_total{state="completed"}')
              == stats["requests"]["completed"]
              and stats["requests"]["completed"] == 5
              and stats["requests"]["failed"] == 0
              and stats["requests"]["shed"] == 0)
        trace = router.obs.tracer.chrome_trace()
        evs = trace["traceEvents"]
        routes = [e for e in evs if e["name"] == "route"]
        attempts = [e for e in evs if e["name"] == "attempt"]
        replay_ids = {e["args"].get("parent") for e in evs
                      if e["name"] == "replay"}
        kill_roots = [e["args"]["id"] for e in routes
                      if e["args"].get("request_id") == "smk-kill"]
        check("trace_route_attempt_replay_chain",
              len(routes) >= 5
              and kill_roots and kill_roots[0] in replay_ids
              and sum(1 for a in attempts
                      if a["args"].get("parent") == kill_roots[0]) == 2)

        # ---- disaggregation rungs (ISSUE 15): two-role fleet, happy ----
        # handoff, severed page stream, prefill-worker death mid-export
        _smoke_disagg(check)
    finally:
        rs.stop()
        for nm, srv in by_name.items():
            if nm != killed.get("name"):
                try:
                    srv.drain_and_join(timeout=60)
                except OSError:
                    pass
    return 1 if fail else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="prefix-affinity router over N serve.py replicas "
                    "(least-loaded placement, circuit breakers, "
                    "mid-stream failover replay)")
    ap.add_argument("--replica", action="append", default=[],
                    metavar="HOST:PORT",
                    help="one serve.py replica (repeatable)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=9000,
                    help="0 = ephemeral (printed at startup)")
    ap.add_argument("--router-config", default="",
                    help="JSON file of RouterConfig overrides")
    ap.add_argument("--smoke", action="store_true",
                    help="in-process 3-replica chaos drill (the `make "
                         "router-chaos-smoke` target)")
    args = ap.parse_args(argv)

    if args.smoke:
        rc = _smoke()
        print(f"router-chaos-smoke: {'PASS' if rc == 0 else 'FAIL'}",
              flush=True)
        return rc

    if not args.replica:
        raise SystemExit("pass at least one --replica HOST:PORT "
                         "(or --smoke)")
    if args.router_config:
        with open(args.router_config) as f:
            cfg = RouterConfig.from_dict(json.load(f))
    else:
        cfg = RouterConfig()
    rs = RouterServer(args.replica, cfg, host=args.host, port=args.port)
    rs.start()
    rs.router._event("routing", port=rs.port,
                     replicas=list(rs.router.replicas))
    try:
        while True:
            time.sleep(1.0)
    except KeyboardInterrupt:
        rs.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
