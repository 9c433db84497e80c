"""Multi-replica router suite (ISSUE 12; docs/SERVING.md "Multi-replica
fabric").

Unit layers first — placement scoring (prefix affinity vs least-loaded),
the circuit-breaker state machine, and the replay splice math — each
driven without HTTP so the properties are exact; then the integration
layers: a real 2-replica fleet with a router-side stream sever (the
connection-drop flavor of a mid-stream death), and the full
``make router-chaos-smoke`` drill (the ISSUE 12 acceptance: 3 in-process
replicas, one killed while holding an in-flight greedy stream, the
spliced client stream bit-identical to an unfaulted run with
``replays == 1`` and every request accounted in the router's registry).
"""

import threading
import time

import pytest

from picotron_tpu.config import RouterConfig
from picotron_tpu.tools import router as router_mod
from picotron_tpu.tools.router import (
    Replica,
    ReplicaFailure,
    RouteRefused,
    Router,
    hist_quantile,
    prefix_key,
)


def _cfg(**kw):
    base = dict(probe_interval_s=0.01, probe_timeout_s=0.2,
                breaker_failures=3, breaker_backoff_s=0.01,
                breaker_backoff_max_s=0.05, breaker_probe_attempts=3,
                scrape_stale_s=10.0, affinity_page_len=16,
                affinity_load_slack=4.0, place_attempts=3,
                replay_budget=2)
    base.update(kw)
    return RouterConfig(**base)


def _router(n=3, **cfg_kw) -> Router:
    """A router over fake replica addresses, probers NOT started; tests
    poke replica state directly."""
    r = Router([f"10.0.0.{i}:80{i}" for i in range(n)], _cfg(**cfg_kw),
               log=lambda *a, **k: None)
    for rep in r.replicas.values():
        _mark_up(r, rep)
    return r


def _mark_up(r: Router, rep: Replica, **scrape):
    with rep._mu:
        rep.ready = True
        rep.draining = False
        rep.scrape = {"queue_depth": 0.0, "active_slots": 0.0,
                      "pool_utilization": 0.0, "ttft_p95": 0.0, **scrape}
        rep.scrape_t = r._clock()


# --------------------------------------------------------------------------- #
# pure helpers
# --------------------------------------------------------------------------- #


def test_prefix_key_is_page_aligned():
    p = list(range(40))
    # < one page: no affinity key (nothing the radix cache could share)
    assert prefix_key(p[:15], 16) is None
    # the key covers whole pages only: 16..31 tokens -> the same one-page key
    assert prefix_key(p[:16], 16) == prefix_key(p[:31], 16)
    # a second full page changes the key
    assert prefix_key(p[:32], 16) != prefix_key(p[:16], 16)
    # the key depends on prefix CONTENT
    q = list(p)
    q[3] = 999
    assert prefix_key(q[:16], 16) != prefix_key(p[:16], 16)


def test_hist_quantile_reads_cumulative_buckets():
    prom = {
        'picotron_ttft_seconds_bucket{le="0.1"}': 50.0,
        'picotron_ttft_seconds_bucket{le="0.2"}': 90.0,
        'picotron_ttft_seconds_bucket{le="0.4"}': 100.0,
        'picotron_ttft_seconds_bucket{le="+Inf"}': 100.0,
        'picotron_ttft_seconds_count': 100.0,
    }
    assert hist_quantile(prom, "picotron_ttft_seconds", 0.50) == 0.1
    assert hist_quantile(prom, "picotron_ttft_seconds", 0.95) == 0.4
    # absent or empty histogram -> 0.0, not a crash
    assert hist_quantile({}, "picotron_ttft_seconds", 0.95) == 0.0
    assert hist_quantile(
        {'x_bucket{le="+Inf"}': 0.0}, "x", 0.95) == 0.0


def test_scrape_tolerates_dp_sharded_worker_fields():
    """ISSUE-18 regression: a dp-sharded worker's /metrics page carries
    picotron_dp_size, per-shard picotron_shard_occupancy{shard} gauges,
    and picotron_slot_migrations_total{outcome} counters next to the
    classic scrape fields. The router's probe extraction (the exact dict
    _probe builds from parse_prometheus) must keep reading the fields it
    knows and stay undisturbed by the new families."""
    from picotron_tpu.obs.metrics import MetricsRegistry, parse_prometheus
    from picotron_tpu.tools.router import tenant_scrape

    reg = MetricsRegistry()
    reg.gauge("picotron_queue_depth").set(3)
    reg.gauge("picotron_active_slots").set(5)
    reg.gauge("picotron_kv_pool_utilization").set(0.25)
    # the new dp-sharded worker surface
    reg.gauge("picotron_dp_size").set(2)
    reg.gauge("picotron_shard_occupancy", shard="0").set(3)
    reg.gauge("picotron_shard_occupancy", shard="1").set(2)
    reg.counter("picotron_slot_migrations_total", outcome="ok").inc(4)
    reg.counter("picotron_slot_migrations_total", outcome="aborted").inc()
    prom = parse_prometheus(reg.prometheus())
    # the new families parsed as labeled samples...
    assert prom["picotron_dp_size"] == 2.0
    assert prom['picotron_shard_occupancy{shard="0"}'] == 3.0
    assert prom['picotron_shard_occupancy{shard="1"}'] == 2.0
    assert prom['picotron_slot_migrations_total{outcome="ok"}'] == 4.0
    # ...and the probe's scrape dict (router.py _probe) is unaffected
    scrape = {
        "queue_depth": prom.get("picotron_queue_depth", 0.0),
        "active_slots": prom.get("picotron_active_slots", 0.0),
        "pool_utilization": prom.get("picotron_kv_pool_utilization", 0.0),
        "ttft_p95": hist_quantile(prom, "picotron_ttft_seconds", 0.95),
        "tenants": tenant_scrape(prom),
    }
    assert scrape == {"queue_depth": 3.0, "active_slots": 5.0,
                      "pool_utilization": 0.25, "ttft_p95": 0.0,
                      "tenants": {}}


def test_router_config_validation():
    RouterConfig().validate()  # defaults are valid
    with pytest.raises(ValueError, match="affinity_page_len"):
        RouterConfig(affinity_page_len=12).validate()
    with pytest.raises(ValueError, match="breaker_backoff_max_s"):
        RouterConfig(breaker_backoff_s=5.0,
                     breaker_backoff_max_s=1.0).validate()
    with pytest.raises(ValueError, match="replay_budget"):
        RouterConfig(replay_budget=-1).validate()
    with pytest.raises(ValueError, match="probe_interval_s"):
        RouterConfig(probe_interval_s=0.0).validate()
    # from_dict ignores unknown keys (the Config policy) and validates
    cfg = RouterConfig.from_dict({"replay_budget": 5, "not_a_knob": 1})
    assert cfg.replay_budget == 5
    with pytest.raises(ValueError, match="place_attempts"):
        RouterConfig.from_dict({"place_attempts": 0})


# --------------------------------------------------------------------------- #
# placement
# --------------------------------------------------------------------------- #


def test_placement_affinity_is_stable_and_shared_prefixes_converge():
    r = _router(3)
    prompt = list(range(32))
    picks = set()
    for _ in range(4):
        rep = r.place(prompt)
        picks.add(rep.name)
        r._request_refused(rep)  # release the inflight slot
    assert len(picks) == 1  # rendezvous: one replica owns this prefix
    # a prompt sharing the page-aligned prefix (different tail) converges
    rep = r.place(prompt + [777])
    r._request_refused(rep)
    assert rep.name in picks
    # a different prefix may land elsewhere, but stays stable too
    other = [7] * 32
    a, b = r.place(other), None
    r._request_refused(a)
    b = r.place(other)
    r._request_refused(b)
    assert a.name == b.name


def test_placement_escapes_affinity_when_overloaded():
    r = _router(3, affinity_load_slack=4.0)
    prompt = list(range(32))
    home = r.place(prompt)
    r._request_refused(home)
    # pile load onto the affinity home beyond the slack: the pick must
    # escape to the least-loaded candidate
    _mark_up(r, home, queue_depth=50.0)
    rep = r.place(prompt)
    r._request_refused(rep)
    assert rep.name != home.name
    # inside the slack the affinity pick still wins
    _mark_up(r, home, queue_depth=2.0)
    rep = r.place(prompt)
    r._request_refused(rep)
    assert rep.name == home.name


def test_placement_drops_stale_open_draining_and_trial_replicas():
    r = _router(3)
    reps = list(r.replicas.values())
    # stale scrape: unknown load is unplaceable load
    with reps[0]._mu:
        reps[0].scrape_t = r._clock() - 1000.0
    # open breaker
    with reps[1]._mu:
        reps[1].breaker = "open"
    # draining: graceful, no placements
    with reps[2]._mu:
        reps[2].draining = True
    assert r.place([1] * 32) is None
    # half-open admits exactly ONE trial at a time
    with reps[2]._mu:
        reps[2].draining = False
        reps[2].breaker = "half_open"
    trial = r.place([1] * 32)
    assert trial is reps[2] and trial.trial
    assert r.place([1] * 32) is None  # the door admits one
    r._request_success(trial)  # trial served -> breaker closes
    with reps[2]._mu:
        assert reps[2].breaker == "closed"


def test_short_prompt_places_least_loaded():
    r = _router(3)
    reps = list(r.replicas.values())
    _mark_up(r, reps[0], queue_depth=9.0)
    _mark_up(r, reps[1], queue_depth=1.0)
    _mark_up(r, reps[2], queue_depth=5.0)
    rep = r.place([1, 2, 3])  # under one page: no affinity key
    r._request_refused(rep)
    assert rep is reps[1]


def test_load_score_weights_metrics_terms():
    r = _router(1, load_queue_weight=1.0, load_slot_weight=0.5,
                load_pool_weight=4.0, load_ttft_weight=2.0)
    rep = next(iter(r.replicas.values()))
    _mark_up(r, rep, queue_depth=3.0, active_slots=2.0,
             pool_utilization=0.5, ttft_p95=0.25)
    with rep._mu:
        rep.inflight = 2
        load = r._load(rep)
    # (3 + 2 inflight) * 1.0 + 2 * 0.5 + 0.5 * 4.0 + 0.25 * 2.0
    assert load == pytest.approx(5.0 + 1.0 + 2.0 + 0.5)


# --------------------------------------------------------------------------- #
# circuit breaker state machine
# --------------------------------------------------------------------------- #


def test_breaker_opens_after_consecutive_failures_and_probe_recovers():
    r = _router(1)
    rep = next(iter(r.replicas.values()))
    assert not r._probe_fail(rep, "x")  # 1
    assert not r._probe_fail(rep, "x")  # 2
    assert r._probe_fail(rep, "x")  # 3 -> open
    with rep._mu:
        assert rep.breaker == "open"
    # one clean probe: open -> half_open
    r._probe_ok(rep, ready=True, draining=False, scrape={})
    with rep._mu:
        assert rep.breaker == "half_open"
    # enough consecutive clean probes close without risking traffic
    r._probe_ok(rep, ready=True, draining=False, scrape={})
    r._probe_ok(rep, ready=True, draining=False, scrape={})
    with rep._mu:
        assert rep.breaker == "closed" and rep.fails == 0


def test_breaker_half_open_trial_failure_reopens():
    r = _router(1)
    rep = next(iter(r.replicas.values()))
    for _ in range(3):
        r._probe_fail(rep, "x")
    r._probe_ok(rep, ready=True, draining=False, scrape={})
    _mark_up(r, rep)
    with rep._mu:
        rep.breaker = "half_open"
    trial = r.place([1] * 32)
    assert trial is rep
    r._request_failure(rep, "trial died")
    with rep._mu:
        assert rep.breaker == "open" and not rep.trial
        assert rep.inflight == 0


def test_intermittent_failures_below_threshold_stay_closed():
    r = _router(1)
    rep = next(iter(r.replicas.values()))
    for _ in range(5):
        r._probe_fail(rep, "flap")
        r._probe_ok(rep, ready=True, draining=False, scrape={})
    with rep._mu:
        assert rep.breaker == "closed"


# --------------------------------------------------------------------------- #
# replay splice (scripted attempts, no HTTP)
# --------------------------------------------------------------------------- #


def _scripted(r: Router, script):
    """Replace ``r._attempt`` with a scripted sequence; records every
    submitted (replica, prompt, max_new) triple. Each script entry is
    ``(outcome, detail, tokens_to_deliver)``."""
    calls = []
    it = iter(script)

    def fake(rep, spec, rid, n, prompt, delivered, max_new, on_token,
             root, tracer, kv_payload=None):
        outcome, detail, toks = next(it)
        calls.append((rep.name, prompt + delivered,
                      max_new - len(delivered)))
        for t in toks:
            delivered.append(t)
            if on_token is not None:
                on_token(t)
        return outcome, detail

    r._attempt = fake
    return calls


def test_replay_resubmits_prompt_plus_delivered_exactly_once():
    r = _router(3)
    prompt = list(range(32))
    calls = _scripted(r, [
        ("failed", "mid-stream death", [100, 101, 102]),
        ("served", "length", [103, 104]),
    ])
    seen = []
    out = r.route({"prompt": prompt, "max_new_tokens": 5}, "rid-1",
                  on_token=seen.append)
    # exactly-once: every token delivered once, spliced in order
    assert seen == [100, 101, 102, 103, 104]
    assert out["tokens"] == seen and out["finish_reason"] == "length"
    assert out["replays"] == 1 and out["attempts"] == 2
    # the replay re-submitted the ORIGINAL prompt + delivered tokens,
    # with the budget reduced by what the client already holds
    assert calls[0] == (calls[0][0], prompt, 5)
    assert calls[1][1] == prompt + [100, 101, 102]
    assert calls[1][2] == 2
    # the failed replica was excluded from the replay placement
    assert calls[1][0] != calls[0][0]
    with r._ctr_mu:
        assert dict(r.requests)["completed"] == 1
    assert int(r._replays.value) == 1


def test_replay_synthesizes_terminal_when_failover_lands_at_the_end():
    # the dead replica delivered every budgeted token but not the done
    # row: the router owes the client a terminal, not a replay of a
    # request with max_new_tokens == 0 (which serve would 400)
    r = _router(3)
    calls = _scripted(r, [("failed", "death after last token", [5, 6, 7])])
    out = r.route({"prompt": [1] * 16, "max_new_tokens": 3}, "rid-2")
    assert out["finish_reason"] == "length" and out["tokens"] == [5, 6, 7]
    assert len(calls) == 1  # no second attempt was needed
    # ... and the eos flavor
    r2 = _router(3)
    _scripted(r2, [("failed", "death on the eos token", [5, 6, 99])])
    out = r2.route({"prompt": [1] * 16, "max_new_tokens": 8,
                    "eos_id": 99}, "rid-3")
    assert out["finish_reason"] == "eos" and out["tokens"] == [5, 6, 99]


def test_replay_refused_by_replica_validation_keeps_partials():
    """A replay the fleet can no longer express — e.g. the replayed
    prompt+delivered fills the replica window, so submit() 400s — must
    terminate ``"error"`` WITH the delivered tokens, not raise a 400
    that eats them (or tear the stream without a done row)."""
    r = _router(3)
    _scripted(r, [
        ("failed", "mid-stream death", [20, 21]),
        ("client_error", "prompt leaves no room to generate", []),
    ])
    out = r.route({"prompt": [1] * 16, "max_new_tokens": 8}, "rid-9")
    assert out["finish_reason"] == "error" and out["tokens"] == [20, 21]
    with r._ctr_mu:
        assert dict(r.requests)["failed"] == 1


def test_replay_budget_exhaustion_fails_with_partial_tokens():
    r = _router(3, replay_budget=1)
    _scripted(r, [
        ("failed", "death 1", [10]),
        ("failed", "death 2", [11]),
    ])
    out = r.route({"prompt": [1] * 16, "max_new_tokens": 8}, "rid-4")
    assert out["finish_reason"] == "error"
    assert out["tokens"] == [10, 11]  # nothing delivered is ever lost
    with r._ctr_mu:
        assert dict(r.requests)["failed"] == 1


def test_refused_placements_are_bounded_and_shed():
    r = _router(3, place_attempts=2)
    _scripted(r, [
        ("refused", "503: queue full", []),
        ("refused", "503: queue full", []),
    ])
    with pytest.raises(RouteRefused) as ei:
        r.route({"prompt": [1] * 16, "max_new_tokens": 4}, "rid-5")
    assert ei.value.status == 503 and ei.value.retry_after >= 1
    with r._ctr_mu:
        assert dict(r.requests)["shed"] == 1
    # refusals never touch the breaker: backpressure is an answer
    for rep in r.replicas.values():
        with rep._mu:
            assert rep.breaker == "closed"


def test_route_refuses_when_no_replica_eligible():
    r = _router(2)
    for rep in r.replicas.values():
        with rep._mu:
            rep.breaker = "open"
    with pytest.raises(RouteRefused) as ei:
        r.route({"prompt": [1, 2, 3], "max_new_tokens": 4}, "rid-6")
    assert ei.value.status == 503
    assert ei.value.retry_after == r.cfg.retry_after_s
    with pytest.raises(RouteRefused) as ei:
        r.route({"prompt": "nope", "max_new_tokens": 4}, "rid-7")
    assert ei.value.status == 400


def test_mid_stream_failure_with_no_survivor_errors_with_partials():
    r = _router(1)
    _scripted(r, [("failed", "only replica died", [42, 43])])
    out = r.route({"prompt": [1] * 16, "max_new_tokens": 8}, "rid-8")
    assert out["finish_reason"] == "error" and out["tokens"] == [42, 43]


# --------------------------------------------------------------------------- #
# integration: real replicas
# --------------------------------------------------------------------------- #


def _fleet(n):
    import jax

    from conftest import make_config
    from picotron_tpu.inference import InferenceEngine
    from picotron_tpu.models import llama
    from picotron_tpu.tools import serve

    servers = []
    for _ in range(n):
        cfg = make_config(dict(
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, hidden_size=32, intermediate_size=64,
            vocab_size=128, max_position_embeddings=64,
            rope_theta=10000.0, dtype="float32", attention_impl="sdpa"),
            seq=32)
        cfg.inference.decode_block_len = 1
        engine = InferenceEngine(cfg, slots=2, max_seq_len=64)
        params = engine.shard_params(jax.jit(
            lambda k, m=cfg.model: llama.init_params(k, m))(
                jax.random.PRNGKey(0)))
        srv = serve.Server(engine, params, port=0,
                           log=lambda *a, **k: None)
        srv.start()
        servers.append(srv)
    return servers


def test_stream_sever_replays_onto_survivor_exactly_once():
    """The connection-drop flavor of a mid-stream death (RouterChaos
    severs the router->replica stream after 3 tokens): the spliced
    client stream is bit-identical to an unfaulted greedy run, no token
    duplicated or dropped, replays accounted."""
    from picotron_tpu.resilience.chaos import RouterChaos
    from picotron_tpu.tools import serve
    from picotron_tpu.tools.router import RouterServer, _stream_post

    servers = _fleet(2)
    names = [f"127.0.0.1:{s.port}" for s in servers]
    chaos = RouterChaos()
    rs = RouterServer(names, _cfg(probe_interval_s=0.05), chaos=chaos,
                      log=lambda *a, **k: None)
    rs.start()
    try:
        assert rs.router.wait_eligible(2, timeout=30)
        spec = {"prompt": [2, 7, 1, 8, 2, 8], "max_new_tokens": 10}
        st, body = serve._post(servers[0].port, spec)  # greedy oracle
        assert st == 200
        oracle = body["tokens"]

        # the request's affinity home is deterministic: sever ITS stream
        home = rs.router.place(spec["prompt"])
        rs.router._request_refused(home)
        chaos.sever_stream(home.name, after_tokens=3)
        st, rows = _stream_post(rs.port, {**spec, "request_id": "sever-1"})
        toks = [r["token"] for r in rows if r.get("event") == "token"]
        done = [r for r in rows if r.get("event") == "done"][0]
        assert st == 200 and toks == oracle == done["tokens"]
        assert done["replays"] == 1 and done["finish_reason"] == "length"
        assert all(r.get("request_id") == "sever-1" for r in rows)
        # the failover excluded the severed home and the survivor served
        # (the home's fail count itself is reset by its next clean probe,
        # so the durable evidence is the replica that finished the job)
        assert done["replica"] != home.name
        stats = rs.router.stats()
        assert stats["replays"] == 1
        assert stats["requests"]["completed"] == 1
    finally:
        rs.stop()
        for s in servers:
            s.drain_and_join(timeout=60)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_router_chaos_smoke_acceptance():
    """The ISSUE 12 acceptance drill end to end (`make
    router-chaos-smoke`): 3 live replicas, one killed while holding an
    in-flight greedy stream -> the client receives the complete
    generation bit-identical to an unfaulted run (replays=1, nothing
    lost); a flapping replica trips the breaker open and recovers
    through half-open with no request erroring; stall, scrape-failure,
    and drain drills; full registry + span-chain accounting."""
    from picotron_tpu.tools import router as rt

    assert rt.main(["--smoke"]) == 0


# --------------------------------------------------------------------------- #
# dynamic replica set (ISSUE 17: the fleet controller's admin surface)
# --------------------------------------------------------------------------- #


def test_remove_replica_joins_prober_and_readd_starts_breaker_fresh():
    """Deregistering mid-stream must not strand the prober thread or leak
    breaker state: the prober is woken through ``gone`` (even out of a
    breaker-open reprobe ladder) and joined; the in-flight route's
    Replica OBJECT stays usable; and re-adding the same address builds a
    fresh closed-breaker replica — the old fails/inflight died with the
    old object."""
    r = _router(3)
    r.start()  # probers run against fake addrs and fail; that's the point
    try:
        name = sorted(r.replicas)[0]
        rep = r.replicas[name]
        # dirty the state exactly as a mid-stream death would
        with rep._mu:
            rep.breaker = "open"
            rep.fails = 7
            rep.inflight = 2
        snap = r.remove_replica(name)
        assert snap["breaker"] == "open" and snap["inflight"] == 2
        assert name not in r.replicas
        assert rep.gone.is_set()
        assert rep._prober is not None and not rep._prober.is_alive()
        assert r.stats()["replicas"].get(name) is None
        # an in-flight route still holds a valid object: bookkeeping on
        # it keeps working after deregistration (it just isn't placeable)
        with rep._mu:
            rep.inflight -= 1
        assert rep.snapshot(r._clock())["inflight"] == 1
        # same address re-registered: nothing carried over. Read as
        # add_replica built it, before its prober runs: against a fake
        # address a probe fails at once, and three of them (30 ms) open the
        # fresh breaker, which a loaded machine let happen ahead of the
        # asserts
        fresh, spawn = {}, r._spawn_prober

        def spawn_after_reading(new):
            with new._mu:
                fresh.update(breaker=new.breaker, fails=new.fails,
                             inflight=new.inflight)
            spawn(new)

        r._spawn_prober = spawn_after_reading
        rep2 = r.add_replica(f"{rep.host}:{rep.port}")
        assert rep2 is not rep
        assert fresh == {"breaker": "closed", "fails": 0, "inflight": 0}
        assert rep2._prober is not None and rep2._prober.is_alive()
        with pytest.raises(router_mod.DuplicateReplica):
            r.add_replica(f"{rep.host}:{rep.port}")
    finally:
        r.stop()


def test_affinity_rehash_on_owner_removal_promotes_hrw_runner_up():
    """Rendezvous pin: removing a prefix's affinity owner re-homes ONLY
    that prefix (to the HRW runner-up over the survivors); prefixes owned
    elsewhere keep their owner — the minimal-disruption property the
    fleet controller's scale-down leans on."""
    r = _router(3)
    page = r.cfg.affinity_page_len
    prompts, before = {}, {}
    for seed in range(12):
        p = [seed * 1000 + j for j in range(page)]
        key = prefix_key(p, page)
        ranked = sorted(r.replicas.values(),
                        key=lambda rep: router_mod._rendezvous(key, rep.name),
                        reverse=True)
        owner = r._affinity_owner(p)
        assert owner is ranked[0]  # owner IS the HRW top, not load-dependent
        prompts[seed], before[seed] = p, owner.name
    victim = sorted(r.replicas)[0]
    assert any(n == victim for n in before.values()), \
        "fixture must exercise the rehash branch"
    r.remove_replica(victim)
    for seed, p in prompts.items():
        key = prefix_key(p, page)
        expect = max(r.replicas.values(),
                     key=lambda rep: router_mod._rendezvous(key, rep.name))
        after = r._affinity_owner(p)
        assert after is expect
        if before[seed] != victim:
            assert after.name == before[seed]  # pinned: unaffected keys stay


def test_replica_set_churn_is_safe_under_concurrent_candidate_scans():
    """The COW contract: candidate scans, snapshots, and stats() racing
    add/remove churn never see a mutating dict or a half-built replica."""
    r = _router(2)
    keep = set(r.replicas)
    stop = threading.Event()
    errs = []

    def reader():
        while not stop.is_set():
            try:
                for rep, _load in r._candidates():
                    rep.snapshot(r._clock())
                r.stats()
            except Exception as e:  # pragma: no cover - the failure mode
                errs.append(repr(e))
                return

    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in readers:
        t.start()
    try:
        for i in range(60):
            rep = r.add_replica(f"10.9.9.9:{8100 + i}")
            _mark_up(r, rep)
            r.remove_replica(rep.name)
    finally:
        stop.set()
        for t in readers:
            t.join(timeout=10)
    assert errs == []
    assert set(r.replicas) == keep
