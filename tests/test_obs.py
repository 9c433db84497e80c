"""Unified telemetry suite (ISSUE 10; docs/OBSERVABILITY.md).

The observability plane's contracts:

- registry correctness under concurrency (N threads hammering counters
  while snapshotters read — totals exact, no lock held across user code);
- histogram bucket-edge semantics (le-inclusive, cumulative rendering,
  +Inf == count) and exact percentiles over the bounded window;
- CounterDict: plain-dict surface, every write mirrored to the registry;
- span tracer: parent links, ring overflow, Chrome-trace JSON validity,
  instant events; trace_dump's validation/chain queries;
- end-to-end: the batcher's /statz numbers == the registry's /metrics
  numbers; one serve request's COMPLETE parented chain in /tracez; a
  timed /profilez capture; train's per-step metrics JSONL ingested by
  extract_metrics without the regex path; obs.enabled: false no-ops.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

import jax

from conftest import make_config
from picotron_tpu import obs as obs_mod
from picotron_tpu.inference import ContinuousBatcher, InferenceEngine, Request
from picotron_tpu.models import llama
from picotron_tpu.obs import (
    GLOBAL_REGISTRY,
    GLOBAL_TRACER,
    MetricsRegistry,
    NullTracer,
    Obs,
    SpanTracer,
)
from picotron_tpu.obs import stalls as stalls_mod
from picotron_tpu.obs import tracing as tracing_mod
from picotron_tpu.obs.metrics import (
    CounterDict,
    NullRegistry,
    parse_prometheus,
)
from picotron_tpu.tools import trace_dump

MAX_LEN = 64

_TINY = dict(
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    hidden_size=32, intermediate_size=64, vocab_size=128,
    max_position_embeddings=MAX_LEN, rope_theta=10000.0, dtype="float32",
    attention_impl="sdpa")


def _engine(slots=2, **inf):
    cfg = make_config(dict(_TINY), seq=32)
    for k, v in inf.items():
        setattr(cfg.inference, k, v)
    engine = InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN)
    params = engine.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    return cfg, engine, params


# --------------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------------- #


def test_counter_concurrency_exact():
    """N threads x M increments with concurrent snapshot/prometheus
    readers: the final value is exactly N*M (no lost updates) and no
    reader ever crashes or deadlocks."""
    reg = MetricsRegistry()
    c = reg.counter("hammer_total", "concurrency test")
    n_threads, m = 8, 500
    stop = threading.Event()
    reader_errs = []

    def reader():
        while not stop.is_set():
            try:
                reg.snapshot()
                reg.prometheus()
            except Exception as e:  # noqa: BLE001 - the assertion payload
                reader_errs.append(e)
                return

    def writer():
        for _ in range(m):
            c.inc()

    readers = [threading.Thread(target=reader) for _ in range(2)]
    writers = [threading.Thread(target=writer) for _ in range(n_threads)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join(30)
    stop.set()
    for t in readers:
        t.join(30)
    assert not reader_errs
    assert c.value == n_threads * m
    assert parse_prometheus(reg.prometheus())["hammer_total"] == n_threads * m


def test_histogram_concurrent_observe_count_exact():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds")
    n_threads, m = 6, 400

    def writer():
        for i in range(m):
            h.observe(1e-4 * (i + 1))

    threads = [threading.Thread(target=writer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    r = h.read()
    assert r["count"] == n_threads * m
    assert sum(r["counts"]) + r["inf"] == r["count"]


def test_histogram_bucket_edges():
    """Prometheus 'le' is INCLUSIVE: a value exactly on a bound lands in
    that bucket; above the last bound lands in +Inf; the cumulative
    rendering ends at _count."""
    reg = MetricsRegistry()
    h = reg.histogram("edges_seconds", buckets=(0.001, 0.01, 0.1))
    for v in (0.001, 0.0005, 0.01, 0.05, 0.1, 99.0):
        h.observe(v)
    r = h.read()
    assert r["counts"] == [2, 1, 2]  # per-bucket, le-inclusive
    assert r["inf"] == 1
    assert r["count"] == 6
    assert r["sum"] == pytest.approx(0.001 + 0.0005 + 0.01 + 0.05 + 0.1 + 99)
    prom = parse_prometheus(reg.prometheus())
    assert prom['edges_seconds_bucket{le="0.001"}'] == 2
    assert prom['edges_seconds_bucket{le="0.01"}'] == 3  # cumulative
    assert prom['edges_seconds_bucket{le="0.1"}'] == 5
    assert prom['edges_seconds_bucket{le="+Inf"}'] == 6
    assert prom["edges_seconds_count"] == 6


def test_histogram_percentiles_window():
    """Exact percentiles over the retained window; the oldest samples
    drop past sample_window (the /statz recent-window semantics)."""
    reg = MetricsRegistry(sample_window=100)
    h = reg.histogram("w_seconds")
    for v in range(1000):  # only the last 100 (900..999) retained
        h.observe(float(v))
    p = h.percentiles()
    assert p["n"] == 100
    assert p["p50"] == pytest.approx(np.percentile(np.arange(900, 1000), 50))
    assert reg.histogram("w_seconds") is h  # get-or-create
    assert reg.histogram("empty_seconds").percentiles() is None


def test_histogram_rejects_bad_buckets():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="strictly increasing"):
        reg.histogram("bad", buckets=(0.1, 0.1))
    with pytest.raises(ValueError, match="already registered"):
        reg.counter("bad")  # name taken by a histogram family


def test_counter_dict_semantics_and_mirror():
    """The exact surface the batcher/serve counters rely on: dict
    equality, dict(), += — with every write mirrored into the labeled
    family (including keys born after construction)."""
    reg = MetricsRegistry()
    d = reg.counter_dict("req_total", ("a", "b"), label="state")
    assert d == {"a": 0, "b": 0}
    d["a"] += 1
    d["a"] += 1
    d["b"] += 1
    d["late"] = 3  # unknown key: plain-dict write + lazy child
    assert dict(d) == {"a": 2, "b": 1, "late": 3}
    prom = parse_prometheus(reg.prometheus())
    assert prom['req_total{state="a"}'] == 2
    assert prom['req_total{state="b"}'] == 1
    assert prom['req_total{state="late"}'] == 3


def test_gauge_and_summary():
    reg = MetricsRegistry()
    reg.gauge("depth").set(7)
    reg.counter("n_total").inc(3)
    reg.histogram("h_seconds").observe(0.5)
    s = reg.summary()
    assert s["depth"] == 7 and s["n_total"] == 3
    assert s["h_seconds"]["count"] == 1
    assert s["h_seconds"]["p50"] == pytest.approx(0.5)


def test_null_registry_and_disabled_obs():
    o = Obs(enabled=False)
    assert isinstance(o.registry, NullRegistry)
    assert isinstance(o.tracer, NullTracer)
    o.registry.counter("x").inc()
    o.registry.histogram("y").observe(1.0)
    with o.tracer.span("s"):
        pass
    assert o.registry.prometheus() == "" and o.registry.snapshot() == {}
    assert o.tracer.spans() == []
    d = CounterDict(o.registry, "z", ("k",))
    d["k"] += 1
    assert d == {"k": 1}  # local dict still authoritative


# --------------------------------------------------------------------------- #
# span tracer + trace_dump
# --------------------------------------------------------------------------- #


def test_span_parent_links_and_chrome_validity():
    tr = SpanTracer(ring=64)
    root = tr.begin("request", uid="r1")
    with tr.span("prefill", parent=root, prompt_tokens=3):
        pass
    tr.record("decode", 1.0, 2.0, parent=root, tokens=4)
    tr.instant("comm/all_reduce", axis="tp")
    tr.end(root, finish_reason="length")
    trace = tr.chrome_trace()
    assert trace_dump.validate(trace) == []
    by_name = {e["name"]: e for e in trace["traceEvents"]}
    rid = by_name["request"]["args"]["id"]
    assert by_name["prefill"]["args"]["parent"] == rid
    assert by_name["decode"]["args"]["parent"] == rid
    assert by_name["decode"]["dur"] == pytest.approx(1e6)
    assert by_name["comm/all_reduce"]["ph"] == "i"
    assert by_name["request"]["ph"] == "X"


def test_span_ring_overflow_keeps_latest():
    tr = SpanTracer(ring=4)
    for i in range(10):
        tr.record(f"s{i}", float(i), float(i) + 0.5)
    names = [s.name for s in tr.spans()]
    assert names == ["s6", "s7", "s8", "s9"]
    tr.resize(8)  # grow-only, retained spans survive
    assert [s.name for s in tr.spans()] == names
    tr.resize(2)  # shrink requests are ignored
    assert len(tr.spans()) == 4


def test_scoped_span_records_exception():
    tr = SpanTracer(ring=8)
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    (s,) = tr.spans()
    assert s.args["error"] == "RuntimeError"


def test_trace_dump_validate_catches_defects():
    assert trace_dump.validate({}) == ["top-level 'traceEvents' must be "
                                       "a list"]
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 1, "pid": 1, "tid": 1},  # no dur
        {"name": "b", "ph": "i", "ts": 1, "pid": 1, "tid": 1,
         "args": {"id": 2, "parent": 99}},  # dangling parent
    ]}
    errs = trace_dump.validate(bad)
    assert any("dur" in e for e in errs)
    # a dangling parent is a WARNING, never a validation error: a live
    # /tracez snapshot has in-flight requests whose root span isn't in
    # the ring yet (it lands at end()), and ring eviction drops old roots
    assert not any("parent" in e for e in errs)
    warns = trace_dump.dangling_parents(bad)
    assert any("parent 99" in w for w in warns)
    assert trace_dump.dangling_parents(
        {"traceEvents": [{"name": "c", "ph": "i", "ts": 0, "pid": 1,
                          "tid": 1, "args": {"id": 5, "parent": 5}}]}) == []


def test_trace_dump_cli_roundtrip(tmp_path):
    tr = SpanTracer(ring=16)
    root = tr.begin("request", uid="u1")
    tr.record("prefill", 0.0, 0.1, parent=root)
    tr.record("decode", 0.1, 0.2, parent=root)
    tr.record("delivery", 0.2, 0.21, parent=root)
    tr.end(root)
    path = tmp_path / "trace.json"
    tr.dump_chrome(str(path))
    assert trace_dump.main([str(path), "--require-request-chain"]) == 0
    assert trace_dump.main([str(path), "--require-request-chain",
                            "u1"]) == 0
    assert trace_dump.main([str(path), "--require-request-chain",
                            "nope"]) == 1
    # an incomplete chain (no delivery) fails the gate
    tr2 = SpanTracer(ring=16)
    r2 = tr2.begin("request", uid="u2")
    tr2.record("prefill", 0.0, 0.1, parent=r2)
    tr2.end(r2)
    p2 = tmp_path / "t2.json"
    tr2.dump_chrome(str(p2))
    assert trace_dump.main([str(p2)]) == 0  # valid, just partial
    assert trace_dump.main([str(p2), "--require-request-chain"]) == 1


def test_trace_dump_lane_chain_audit(tmp_path):
    """The mixed-dispatch lane gate: ``lane`` spans must parent to a
    request root and tile the prompt — chunk numbers 1..n, each chunk
    starting where the previous ended, the last landing at the lane
    prefill span's prompt_tokens. Gaps, bad numbering, or a short final
    chunk fail ``--require-lane-chain``."""
    tr = SpanTracer(ring=32)
    root = tr.begin("request", uid="u1")
    pf = tr.begin("prefill", parent=root, uid="u1", prompt_tokens=20,
                  lane=True)
    tr.record("lane", 0.0, 0.1, parent=root, chunk=1, start=0, end=8,
              slot=0)
    tr.record("lane", 0.1, 0.2, parent=root, chunk=2, start=8, end=16,
              slot=0)
    tr.record("lane", 0.2, 0.3, parent=root, chunk=3, start=16, end=20,
              slot=0)
    tr.end(pf, dispatches=3, lane=True)
    tr.end(root)
    good = tmp_path / "lane.json"
    tr.dump_chrome(str(good))
    la = trace_dump.lane_chain(trace_dump.load(str(good)))
    assert la == {"lanes": 3, "linked": 3, "errors": []}
    assert trace_dump.main([str(good), "--require-lane-chain"]) == 0

    # a gap between chunks (8 -> 12) and a short final chunk both fail
    tr2 = SpanTracer(ring=32)
    r2 = tr2.begin("request", uid="u2")
    pf2 = tr2.begin("prefill", parent=r2, uid="u2", prompt_tokens=20,
                    lane=True)
    tr2.record("lane", 0.0, 0.1, parent=r2, chunk=1, start=0, end=8,
               slot=0)
    tr2.record("lane", 0.1, 0.2, parent=r2, chunk=2, start=12, end=18,
               slot=0)
    tr2.end(pf2, dispatches=2, lane=True)
    tr2.end(r2)
    bad = tmp_path / "lane_bad.json"
    tr2.dump_chrome(str(bad))
    la2 = trace_dump.lane_chain(trace_dump.load(str(bad)))
    assert any("starts at 12" in e for e in la2["errors"])
    assert any("prompt has 20 tokens" in e for e in la2["errors"])
    assert trace_dump.main([str(bad), "--require-lane-chain"]) == 1
    # no lane spans at all: the gate reports the likely cause
    empty = tmp_path / "none.json"
    tr3 = SpanTracer(ring=4)
    r3 = tr3.begin("request", uid="u3")
    tr3.end(r3)
    tr3.dump_chrome(str(empty))
    assert trace_dump.main([str(empty), "--require-lane-chain"]) == 1


# --------------------------------------------------------------------------- #
# engine/batcher integration
# --------------------------------------------------------------------------- #


def test_batcher_stats_agree_with_registry():
    """/statz and /metrics are two renderings of the SAME instruments:
    the counters, token totals, dispatch counts, and percentile payloads
    must agree exactly."""
    GLOBAL_TRACER.clear()
    cfg, engine, params = _engine(slots=2)
    b = ContinuousBatcher(engine, params)
    b.run([Request(f"q{i}", [3 + i, 7 + i], max_new_tokens=4)
           for i in range(3)])
    s = b.stats()
    prom = parse_prometheus(engine.obs.registry.prometheus())
    assert prom['picotron_requests_total{state="completed"}'] == \
        s["completed"] == 3
    assert prom['picotron_requests_total{state="admitted"}'] == 3
    assert prom["picotron_generated_tokens_total"] == \
        s["generated_tokens"] == 12
    assert prom['picotron_dispatch_total{kind="prefill"}'] == \
        s["prefill_dispatches"]
    assert prom["picotron_queue_wait_seconds_count"] == \
        s["queue_wait_s"]["n"] == 3
    assert prom["picotron_ttft_seconds_count"] == s["ttft_s"]["n"] == 3
    assert prom["picotron_queue_depth"] == 0
    assert prom["picotron_active_slots"] == 0
    # dispatch latency histogram counted one entry per decode dispatch
    assert prom['picotron_dispatch_seconds_count{kind="decode"}'] == \
        b.decode_dispatches
    # the span ring holds each request's prefill + >= 1 decode child
    chains = trace_dump.request_chains(GLOBAL_TRACER.chrome_trace())
    assert set(chains) == {"q0", "q1", "q2"}
    for c in chains.values():
        assert c["queue_wait"] and c["prefill"] and c["dispatches"] >= 1


def test_speculative_round_spans_carry_accept_counts():
    GLOBAL_TRACER.clear()
    cfg, engine, params = _engine(slots=2, spec_len=3)
    b = ContinuousBatcher(engine, params)
    b.run([Request("s0", [5, 6, 7], max_new_tokens=6)])
    prom = parse_prometheus(engine.obs.registry.prometheus())
    assert prom["picotron_draft_proposed_total"] == b.draft_proposed > 0
    assert prom["picotron_draft_accepted_total"] == b.draft_accepted
    # a verify is a round like a decode block: its four parts, once each
    assert {k: v for k, v in prom.items()
            if k.startswith("picotron_round_part_seconds_count")} == {
        f'picotron_round_part_seconds_count{{part="{p}"}}':
        b.decode_dispatches for p in ("issue/operands", "issue/enqueue",
                                      "sync/wait", "sync/fetch")}
    verifies = [s for s in GLOBAL_TRACER.spans() if s.name == "verify"]
    assert verifies and all("accepted" in s.args and
                            s.args["draft_len"] == 3 for s in verifies)


def test_obs_disabled_batcher_runs_and_records_nothing():
    cfg = make_config(dict(_TINY), seq=32)
    cfg.obs.enabled = False
    engine = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN)
    params = engine.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    b = ContinuousBatcher(engine, params)
    res = b.run([Request("q", [3, 4, 5], max_new_tokens=4)])
    assert res["q"].finish_reason == "length" and res["q"].span_id is None
    assert b.counters["completed"] == 1  # the dict view still works
    assert engine.obs.registry.prometheus() == ""
    s = b.stats()
    assert s["queue_wait_s"] is None and s["ttft_s"] is None
    # the stall judge is its null form: no history, no counters, no record
    watch = engine.obs.stalls
    assert not watch.enabled and s["stalls"] == {}
    assert watch.judge("step/sync", "decode", 9.0) is None
    assert watch.take_slow() == [] and "picotron_stall" not in \
        engine.obs.registry.prometheus()


def test_obs_disabled_output_identical():
    """The acceptance bit: obs off produces byte-identical generations to
    obs on (the instruments never touch the PRNG chain or the dispatch
    path)."""
    reqs = [Request(f"q{i}", [3 + i, 9 + i], max_new_tokens=6,
                    temperature=0.8) for i in range(3)]
    _, e_on, p_on = _engine(slots=2)
    on = ContinuousBatcher(e_on, p_on, seed=11).run(
        [Request(**vars(r)) for r in reqs])
    cfg = make_config(dict(_TINY), seq=32)
    cfg.obs.enabled = False
    e_off = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN)
    p_off = e_off.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    off = ContinuousBatcher(e_off, p_off, seed=11).run(
        [Request(**vars(r)) for r in reqs])
    for uid in on:
        assert on[uid].tokens == off[uid].tokens
        assert on[uid].finish_reason == off[uid].finish_reason


# --------------------------------------------------------------------------- #
# serve integration: /metrics, /tracez, /profilez
# --------------------------------------------------------------------------- #


def _server(slots=2, **front_kw):
    from picotron_tpu.tools import serve

    cfg, engine, params = _engine(slots=slots)
    front_kw.setdefault("log", lambda *a, **k: None)
    srv = serve.Server(engine, params, port=0, **front_kw)
    srv.start()
    return cfg, srv


def test_serve_metrics_tracez_profilez(tmp_path):
    from picotron_tpu.tools import serve

    GLOBAL_TRACER.clear()
    cfg, srv = _server()
    try:
        port = srv.port
        st, body = serve._post(port, {"prompt": [1, 2, 3],
                                      "max_new_tokens": 5, "uid": "m1"})
        assert st == 200
        st, stats = serve._get(port, "/statz")
        mst, mtext = serve._get_text(port, "/metrics")
        assert mst == 200
        prom = parse_prometheus(mtext)
        assert prom['picotron_requests_total{state="completed"}'] == \
            stats["completed"]
        assert prom['picotron_rejections_total{reason="queue_full"}'] == 0
        # the round's phases under the labels they had, its parts (ISSUE
        # 37) in a family of their own, and no second reading of the sync
        labels = lambda family: {k.split('"')[1] for k in prom
                                 if k.startswith(family + "_count{")}
        served = {"step/plan", "step/admit", "step/issue", "step/sync",
                  "step/deliver", "loop/lock_wait", "loop/results"}
        assert served <= labels("picotron_round_phase_seconds") \
            <= served | {"loop/idle"}
        assert labels("picotron_round_part_seconds") == {
            "issue/operands", "issue/enqueue", "sync/wait", "sync/fetch"}
        # a round's host-device copies (ISSUE 38): one up and one down a
        # dispatch, a family of two labels
        assert {k: v for k, v in prom.items()
                if k.startswith("picotron_round_copies_total")} == {
            f'picotron_round_copies_total{{direction="{d}"}}':
            prom['picotron_dispatch_seconds_count{kind="decode"}']
            for d in ("h2d", "d2h")}
        assert "picotron_host_sync_seconds" not in mtext
        # the model-memory gauge (ISSUE 13): /statz and /metrics agree on
        # resident weight bytes — what the router's scrape reads to see
        # per-replica model memory (int8 replicas report ~half bf16)
        assert stats["weight_bytes"] == srv.front.weight_bytes > 0
        assert stats["weight_dtype"] == "bf16"
        assert prom["picotron_weight_bytes"] == stats["weight_bytes"]
        # what the cache is (ISSUE 31): the heads a 128-lane row of K and
        # V holds (the tiny model's 4 heads of 8 are not 16: a head a row)
        # and the resident leaves' bytes, on both pages
        cache = srv.front._batcher.engine.init_cache()
        assert prom["picotron_kv_pack_factor"] == stats["kv_pack_factor"] \
            == 1
        assert prom["picotron_kv_cache_bytes"] == stats["kv_cache_bytes"] \
            == sum(a.nbytes for a in jax.tree.leaves(cache))
        # /tracez: the request's chain is COMPLETE (queue wait ->
        # prefill -> >= 1 dispatch -> delivery), all parented
        tst, trace = serve._get(port, "/tracez")
        assert tst == 200 and trace_dump.validate(trace) == []
        chains = trace_dump.request_chains(trace)
        assert chains["m1"]["complete"], chains
        # /profilez: one timed capture lands real files; a second start
        # while running is 409
        prof = tmp_path / "prof"
        pst, pbody = serve._profilez_post(
            port, {"seconds": 0.8, "dir": str(prof)})
        assert pst == 200 and pbody["ok"]
        pst2, pbody2 = serve._profilez_post(
            port, {"seconds": 0.8, "dir": str(prof)})
        assert pst2 == 409 and "already running" in pbody2["error"]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and srv.front.profiler.running:
            time.sleep(0.05)
        assert srv.front.profiler.captures == 1
        assert prof.is_dir() and list(prof.iterdir())
        pst3, pbody3 = serve._profilez_post(port, {"seconds": -1})
        assert pst3 == 400 and "seconds" in pbody3["error"]
    finally:
        srv.drain_and_join(timeout=60)


# --------------------------------------------------------------------------- #
# train integration: metrics JSONL + trace dump
# --------------------------------------------------------------------------- #


def _train_cfg(tmp_path, **obs_kw):
    cfg = make_config(dict(_TINY), seq=32, total_train_steps=4)
    for k, v in obs_kw.items():
        setattr(cfg.obs, k, v)
    return cfg


def test_train_writes_metrics_jsonl_and_trace(tmp_path):
    from picotron_tpu.tools import extract_metrics as em
    from picotron_tpu.train import train

    run = tmp_path / "run_dp1_tp1_mbs2_sl32"
    run.mkdir()
    cfg = _train_cfg(tmp_path,
                     metrics_jsonl=str(run / "metrics.jsonl"),
                     trace_path=str(run / "trace.json"))
    step, tokens, loss = train(cfg)
    assert step == 4
    rows = em.parse_jsonl_file(str(run / "metrics.jsonl"))
    assert [r["step"] for r in rows] == [1, 2, 3, 4]
    assert all(np.isfinite(r["loss"]) for r in rows)
    # the terminal summary row carries the registry snapshot and is NOT
    # a step row
    last = [json.loads(l) for l in
            open(run / "metrics.jsonl") if l.strip()][-1]
    assert last.get("event") == "summary"
    assert "picotron_train_dispatch_seconds" in last["metrics"]
    # extract_metrics ingests the run WITHOUT any log present (and with
    # a decoy log whose regex rows would disagree, the JSONL wins)
    (run / "log.out").write_text(
        "Step: 9 | Loss: 1.0 | Global batch size: 1 | "
        "Tokens/s: 1.00K | Tokens/s/chip: 1.00K | Tokens: 1\n")
    out = em.extract(str(tmp_path))
    assert len(out) == 1
    assert out[0]["num_steps"] == 1  # 4 steps - 3 warmup
    assert out[0]["final_loss"] == pytest.approx(rows[-1]["loss"])
    # the dumped trace is valid Chrome-trace JSON with train spans
    trace = trace_dump.load(str(run / "trace.json"))
    assert trace_dump.validate(trace) == []
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"train/dispatch", "data", "dispatch", "host_sync"} <= names


def test_train_metrics_jsonl_env_override(tmp_path, monkeypatch):
    from picotron_tpu.train import train

    env_path = tmp_path / "env.jsonl"
    monkeypatch.setenv("PICOTRON_METRICS_JSONL", str(env_path))
    cfg = _train_cfg(tmp_path, metrics_jsonl=str(tmp_path / "cfg.jsonl"))
    train(cfg, max_steps_override=2)
    assert env_path.exists()  # the supervisor's export wins
    assert not (tmp_path / "cfg.jsonl").exists()


def test_train_obs_disabled_writes_nothing(tmp_path):
    from picotron_tpu.train import train

    cfg = _train_cfg(tmp_path, enabled=False,
                     metrics_jsonl=str(tmp_path / "m.jsonl"),
                     trace_path=str(tmp_path / "t.json"))
    step, _, loss = train(cfg, max_steps_override=2)
    assert step == 2 and np.isfinite(loss)
    assert not (tmp_path / "m.jsonl").exists()
    assert not (tmp_path / "t.json").exists()


# --------------------------------------------------------------------------- #
# resilience + comm_trace feeds
# --------------------------------------------------------------------------- #


def test_retry_counts_into_global_registry():
    from picotron_tpu.resilience.retry import retry

    before = GLOBAL_REGISTRY.counter(
        "picotron_retries_total", desc="obs-test").value
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("flake")
        return "ok"

    assert retry(flaky, attempts=3, backoff=0, jitter=0,
                 desc="obs-test", sleep=lambda s: None) == "ok"
    after = GLOBAL_REGISTRY.counter(
        "picotron_retries_total", desc="obs-test").value
    assert after - before == 2  # two failed attempts counted


def test_emergency_save_outcomes_counted():
    from picotron_tpu.resilience.preemption import PreemptionGuard

    def val(outcome):
        return GLOBAL_REGISTRY.counter(
            "picotron_emergency_saves_total", outcome=outcome).value

    g = PreemptionGuard()
    c0, f0 = val("completed"), val("failed")
    assert g.emergency_save(lambda: None) is True
    with pytest.raises(RuntimeError):
        g.emergency_save(lambda: (_ for _ in ()).throw(RuntimeError("x")))
    assert val("completed") == c0 + 1
    assert val("failed") == f0 + 1


def test_comm_trace_records_instant_events(monkeypatch, capsys):
    from picotron_tpu import comm_trace

    GLOBAL_TRACER.clear()
    monkeypatch.setenv("PICOTRON_VERBOSE", "1")
    x = np.ones((2, 4), np.float32)
    out = comm_trace.log("all_reduce", "tp", x)
    assert out is x  # identity on the value, as before
    (s,) = [s for s in GLOBAL_TRACER.spans()
            if s.name == "comm/all_reduce"]
    assert s.args["axis"] == "tp" and s.args["shape"] == "(2, 4)"
    assert "[comm] all_reduce" in capsys.readouterr().err
    # verbose off: no stderr line AND no span
    GLOBAL_TRACER.clear()
    monkeypatch.setenv("PICOTRON_VERBOSE", "0")
    comm_trace.log("all_gather", "tp", x)
    assert GLOBAL_TRACER.spans() == []


# --------------------------------------------------------------------------- #
# ISSUE 25: the capture as a control, spans on the profiler's clock, the
# dispatch loop's phases
# --------------------------------------------------------------------------- #


def _host_events(trace_dir):
    """{name: [(start_ns, duration_ns, stats)]} of the host planes' events
    in the newest capture under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        str(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("pt"):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.duration_ns, dict(e.stats)))
    return out


def test_capture_is_a_control_start_stop_busy_and_timed(tmp_path):
    from picotron_tpu.obs import ProfileCapture, tracing

    cap = ProfileCapture(str(tmp_path / "a"), seconds=0.3,
                         tracer=SpanTracer(ring=8))
    # nothing open: stop answers not-ok and changes nothing
    assert cap.stop()["ok"] is False and not cap.running
    assert not tracing.capture_open()
    # explicit: open until stop(), a second start answers busy
    t_before = time.monotonic()
    started = cap.start()
    assert started == {"ok": True, "dir": str(tmp_path / "a"),
                       "seconds": None}
    assert cap.running and tracing.capture_open()
    busy = cap.start()
    assert busy["ok"] is False and "already running" in busy["error"]
    time.sleep(0.5)  # longer than `seconds`: no timer was armed
    assert cap.running
    stopped = cap.stop()
    assert stopped["ok"] and stopped["dir"] == str(tmp_path / "a")
    assert t_before <= stopped["t_start"] < stopped["t_stop"] \
        <= time.monotonic()
    assert stopped["t_stop"] - stopped["t_start"] >= 0.5
    assert not cap.running and not tracing.capture_open()
    assert cap.captures == 1 and cap.stop()["ok"] is False
    # both anchors are in the trace, each with the ring clock's reading
    anchors = _host_events(tmp_path / "a")["pt.anchor"]
    assert len(anchors) == 2 and all("ring_ns" in a[2] for a in anchors)
    # timed: closes itself through the same stop(); bad lengths refused
    assert cap.start(seconds=0)["ok"] is False
    assert cap.start(str(tmp_path / "b"), seconds=0.3)["ok"]
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and cap.running:
        time.sleep(0.05)
    assert not cap.running and cap.captures == 2
    assert not tracing.capture_open()
    assert list((tmp_path / "b").iterdir())
    # an explicit stop beats the timer, whose late stop then closes nothing
    assert cap.start(str(tmp_path / "c"), seconds=0.2)["ok"]
    assert cap.stop()["ok"]
    assert cap.start(str(tmp_path / "d"))["ok"]  # a new, open-ended one
    time.sleep(0.4)  # the first capture's timer has fired by now
    assert cap.running
    assert cap.stop()["ok"] and cap.captures == 4


def test_scoped_spans_land_in_the_capture_on_its_clock(tmp_path):
    """A scoped span is written twice while a capture is open: ``pt:`` on
    a claimed loop thread, ``pt.req:`` on any other; args stay on the ring
    span; the anchor shifts the ring twin onto the trace's clock."""
    from picotron_tpu.obs import ProfileCapture

    tr = SpanTracer(ring=64)
    cap = ProfileCapture(str(tmp_path), tracer=tr)
    with tr.span("before"):  # no capture yet: ring only
        pass
    assert cap.start()["ok"]

    def loop():
        tr.claim_loop_thread()
        try:
            with tr.span("step/plan", slots=3):
                time.sleep(0.02)
                with tr.span("step/admit"):
                    time.sleep(0.01)
        finally:
            tr.release_loop_thread()

    def handler():
        with tr.span("submit/lock_wait", uid="u1"):
            time.sleep(0.015)

    for fn in (loop, handler):
        th = threading.Thread(target=fn)
        th.start()
        th.join(timeout=30)
        assert not th.is_alive()
    began = tr.begin("request")  # begin/end and record(): ring only
    tr.end(began)
    assert cap.stop()["ok"]
    with tr.span("after"):
        pass
    ev = _host_events(tmp_path)
    assert set(ev) == {"pt.anchor", "pt:step/plan", "pt:step/admit",
                       "pt.req:submit/lock_wait"}
    assert all(len(v) == 1 for k, v in ev.items() if k != "pt.anchor")
    # the anchor's shift puts every ring twin within 1 ms of its event
    a_start, _, a_stats = ev["pt.anchor"][0]
    shift = a_start - a_stats["ring_ns"]
    ring = {s.name: s for s in tr.spans()}
    assert ring["step/plan"].args == {"slots": 3}
    for name, twin in (("pt:step/plan", "step/plan"),
                       ("pt:step/admit", "step/admit"),
                       ("pt.req:submit/lock_wait", "submit/lock_wait")):
        start, dur, _ = ev[name][0]
        s = ring[twin]
        assert abs(s.t0 * 1e9 + shift - start) < 1e6, name
        assert abs(s.t1 * 1e9 + shift - (start + dur)) < 1e6, name


def test_no_capture_no_annotation_and_null_tracer_writes_nothing(
        tmp_path, monkeypatch):
    from picotron_tpu.obs import ProfileCapture

    made = []

    class Counting:
        def __init__(self, name, **kw):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    tr = SpanTracer(ring=8)
    with tr.span("closed"):
        pass
    assert made == []  # one global read, nothing constructed
    # obs.enabled: false under an open capture: neither write happens
    cap = ProfileCapture(str(tmp_path), tracer=NullTracer())
    assert cap.start()["ok"]
    try:
        off = obs_mod.null_obs()
        with off.phase("loop/results"):
            pass
        with off.tracer.span("x"):
            pass
        assert made == []
        with tr.span("open"):  # a live tracer does write while it is open
            pass
        assert made == ["pt.req:open"]
    finally:
        assert cap.stop()["ok"]
    assert off.registry.prometheus() == ""


class _ManualClock:
    """Advances only when told, so a phase's seconds are exactly what the
    test put into it and the boundaries between phases cost nothing."""

    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t


def _phase_reads(registry, family="picotron_round_phase_seconds"):
    """{label value: {"sum", "count"}} of one labelled histogram family."""
    snap = parse_prometheus(registry.prometheus())
    out = {}
    for key, v in snap.items():
        if key.startswith(family + "_") and "_bucket" not in key:
            kind, _, label = key[len(family) + 1:].partition("{")
            out.setdefault(label.split('"')[1], {})[kind] = v
    return out


@pytest.mark.parametrize("overlap", [False, True])
def test_round_phases_tile_the_step(overlap, monkeypatch):
    """N rounds give N observations of every phase, and the phases' sums
    add up to the steps' wall time: plan (around admit), admit, issue,
    sync and deliver tile the round, in the serial and the pipelined
    step alike."""
    cfg, engine, params = _engine(slots=2, overlap=overlap,
                                  decode_block_len=2)
    clock = _ManualClock()
    engine.obs = Obs(enabled=True, registry=MetricsRegistry(),
                     tracer=SpanTracer(ring=4096, clock=clock))
    b = ContinuousBatcher(engine, params, clock=clock)

    def costing(obj, name, seconds):
        inner = getattr(obj, name)

        def wrapped(*a, **kw):
            clock.t += seconds
            return inner(*a, **kw)

        monkeypatch.setattr(obj, name, wrapped)

    costing(b, "_expire_deadlines", 1e-3)   # step/plan, before admit
    costing(b, "_admit", 2e-3)              # step/admit
    costing(engine, "decode_block", 3e-3)   # step/issue
    costing(jax, "block_until_ready", 5e-3)  # step/sync: its one wait
    costing(b, "_tokens_done", 0.5e-3)      # step/deliver, once a slot
    #                          and round (and admit's first token, alone)
    for i in range(3):
        b.submit(Request(f"p{i}", [3 + i, 5, 7], max_new_tokens=9))
    rounds, wall = 0, 0.0
    while b.busy:
        t0 = clock()
        b.step()
        wall += clock() - t0
        rounds += 1
    assert rounds >= 4 and not b.take_results().keys() ^ {"p0", "p1", "p2"}
    ph = _phase_reads(engine.obs.registry)
    assert set(ph) == {"step/plan", "step/admit", "step/issue",
                       "step/sync", "step/deliver"}
    assert ph["step/plan"]["count"] == ph["step/admit"]["count"] == rounds
    n = b.decode_dispatches
    assert n in (rounds, rounds - 1)  # overlap: the last step only drains
    for p in ("step/issue", "step/sync", "step/deliver"):
        assert ph[p]["count"] == n, p
    assert ph["step/plan"]["sum"] == pytest.approx(rounds * 1e-3)
    assert ph["step/issue"]["sum"] == pytest.approx(n * 3e-3)
    assert ph["step/sync"]["sum"] == pytest.approx(n * 5e-3)
    # 3 first tokens at admission, then blocks of 2: 9 = 1 + 4 x 2
    events = b._stream_events_total.value
    assert events == 3 * 5 and b.generated_tokens == 3 * 9
    assert ph["step/admit"]["sum"] + ph["step/deliver"]["sum"] \
        == pytest.approx(rounds * 2e-3 + events * 0.5e-3)
    assert sum(v["sum"] for v in ph.values()) == pytest.approx(wall)
    # the ring holds the same tiles: in time order they abut
    tiles = sorted((s for s in engine.obs.tracer.spans()
                    if s.name.startswith("step/")), key=lambda s: s.t0)
    assert sum(s.t1 - s.t0 for s in tiles) == pytest.approx(wall)


def test_contended_submit_is_the_request_roots_first_child():
    """A handler thread that waits for the front end's lock observes the
    wait, and the wait opens the request's chain: the root begins where
    the wait began and the wait span is its first child."""
    from picotron_tpu.tools import serve

    GLOBAL_TRACER.clear()
    cfg, srv = _server()
    front = srv.front
    try:
        assert front._mu.acquire(timeout=10)
        try:
            got = {}
            th = threading.Thread(target=lambda: got.update(
                r=serve._post(srv.port, {"prompt": [1, 2, 3], "uid": "w1",
                                         "max_new_tokens": 3})))
            th.start()
            time.sleep(0.25)
        finally:
            front._mu.release()
        th.join(timeout=60)
        assert not th.is_alive() and got["r"][0] == 200
        prom = parse_prometheus(front.metrics_text())
        assert prom["picotron_submit_lock_wait_seconds_count"] == 1
        assert prom["picotron_submit_lock_wait_seconds_sum"] >= 0.2
        spans = GLOBAL_TRACER.spans()
        root = next(s for s in spans
                    if s.name == "request" and s.args.get("uid") == "w1")
        kids = sorted((s for s in spans if s.parent_id == root.span_id),
                      key=lambda s: s.t0)
        assert kids[0].name == "submit/lock_wait"
        assert kids[0].t0 == root.t0 and kids[0].duration_s >= 0.2
        assert {"queue_wait", "prefill", "delivery"} \
            <= {s.name for s in kids}
        chains = trace_dump.request_chains(GLOBAL_TRACER.chrome_trace())
        assert chains["w1"]["complete"]
        # the loop's own phases were observed on the way
        for phase in ("loop/lock_wait", "loop/results", "loop/idle",
                      "step/plan", "step/deliver"):
            assert prom['picotron_round_phase_seconds_count'
                        f'{{phase="{phase}"}}'] >= 1, phase
    finally:
        srv.drain_and_join(timeout=60)


def test_shed_submit_keeps_its_wait_span_with_the_error():
    from picotron_tpu.tools import serve

    GLOBAL_TRACER.clear()
    cfg, engine, params = _engine(slots=2)
    front = serve.FrontEnd(engine, params, log=lambda *a, **k: None,
                           stall_timeout_s=0)  # no watchdog: one slice

    class Wedged:  # the dispatch loop never lets go
        def acquire(self, timeout=None):
            return False

    front._mu = Wedged()
    with pytest.raises(serve.AdmissionError) as e:
        front.submit({"prompt": [1, 2], "uid": "s1"})
    assert e.value.status == 503 and front.rejections["stalled"] == 1
    wait, = [s for s in GLOBAL_TRACER.spans()
             if s.name == "submit/lock_wait"]
    assert wait.args == {"uid": "s1", "error": "AdmissionError"}
    prom = parse_prometheus(engine.obs.registry.prometheus())
    assert prom["picotron_submit_lock_wait_seconds_count"] == 1


@pytest.mark.parametrize("verdict", ["long_step", "stalled", "no_watchdog"])
def test_submit_waits_in_slices_and_sheds_on_the_watchdogs_verdict(verdict):
    """A step longer than one slice is waited out; the wait ends with 503
    at the end of the slice that finds ``stalled`` set, or, with no
    watchdog to say so, once it is as long as a step may take."""
    from picotron_tpu.tools import serve

    cfg, engine, params = _engine(slots=2)
    front = serve.FrontEnd(engine, params, log=lambda *a, **k: None,
                           stall_timeout_s=60.0)
    front.SUBMIT_WAIT_SLICE_S = 0.01
    real, asked = front._mu, []

    class Busy:  # the loop holds the lock through three slices
        def acquire(self, timeout=None):
            asked.append(timeout)
            if verdict == "stalled" and len(asked) == 3:
                front.stalled = True
            if verdict == "long_step" and len(asked) == 4:
                return real.acquire(timeout=timeout)
            return False

        def release(self):
            real.release()

    front._mu = Busy()
    if verdict == "long_step":
        uid, _ = front.submit({"prompt": [1, 2], "uid": "s1"})
        assert uid == "s1" and asked == [0.01] * 4
        assert front.rejections["stalled"] == 0
        return
    if verdict == "no_watchdog":
        front.stall_timeout_s = 0.05
    with pytest.raises(serve.AdmissionError) as e:
        front.submit({"prompt": [1, 2], "uid": "s1"})
    assert e.value.status == 503 and front.rejections["stalled"] == 1
    if verdict == "stalled":
        assert len(asked) == 3


def _prefill_counts(engine):
    prom = parse_prometheus(engine.obs.registry.prometheus())
    return (prom.get("picotron_prefill_tokens_total", 0),
            prom.get('picotron_dispatch_seconds_count{kind="prefill"}', 0))


def test_prefill_tokens_total_solo_chunked_lane_and_cached_prefix():
    """Prompt tokens that ran through a prefill program: every prompt
    token of a solo and of a chunked prefill, the lane's chunks, and on
    the paged layout the prompt less its radix-cached prefix."""
    long_a = [(5 * i + 2) % 120 + 1 for i in range(20)]
    long_b = [(3 * i + 7) % 120 + 1 for i in range(17)]
    # solo (one bucketed program) and chunked (prompt > prefill_chunk)
    cfg, engine, params = _engine(slots=2, prefill_chunk=8)
    b = ContinuousBatcher(engine, params)
    b.run([Request("solo", [3, 4, 5, 6, 7], max_new_tokens=2),
           Request("chunked", long_a, max_new_tokens=2)])
    assert _prefill_counts(engine) == (5 + 20, 2)
    assert b.prefill_dispatches == 1 + 3
    # the fused lane: no solo prefill dispatch, the same tokens counted
    cfg, engine, params = _engine(slots=2, prefill_chunk=8,
                                  decode_block_len=4, mixed_dispatch=True)
    b = ContinuousBatcher(engine, params)
    b.run([Request("a", long_a, max_new_tokens=6),
           Request("b", long_b, max_new_tokens=6)])
    prom = parse_prometheus(engine.obs.registry.prometheus())
    lane = sum(v for k, v in prom.items()
               if k.startswith("picotron_prefill_lane_tokens_total"))
    assert lane > 0
    assert _prefill_counts(engine)[0] == 20 + 17
    # paged: the second request shares a radix-cached prefix
    cfg, engine, params = _engine(slots=2, kv_layout="paged",
                                  kv_page_len=8)
    b = ContinuousBatcher(engine, params)
    b.run([Request("first", long_a, max_new_tokens=2)])
    assert _prefill_counts(engine)[0] == 20
    b.run([Request("second", long_a[:16] + [9, 9, 9], max_new_tokens=2)])
    cached = [s.args["cached_tokens"] for s in GLOBAL_TRACER.spans()
              if s.name == "prefill" and s.args.get("uid") == "second"][-1]
    assert cached == 16
    assert _prefill_counts(engine)[0] == 20 + 19 - cached


def test_train_window_and_loop_spans_go_through_the_capture(tmp_path):
    """``logging.profile_start/stop`` is the same control, and a capture
    of a training run holds its loop's scoped spans as ``pt:``."""
    from picotron_tpu.obs import tracing
    from picotron_tpu.train import train

    cfg = _train_cfg(tmp_path)
    cfg.logging.profile_start = 2
    cfg.logging.profile_stop = 3
    cfg.logging.profile_dir = str(tmp_path / "prof")
    step, _, loss = train(cfg)
    assert step == 4 and np.isfinite(loss) and not tracing.capture_open()
    ev = _host_events(tmp_path / "prof")
    assert {"pt.anchor", "pt:train/dispatch", "pt:data", "pt:dispatch",
            "pt:host_sync"} <= set(ev)
    assert len(ev["pt:train/dispatch"]) == 1  # steps [2, 3): one dispatch
    assert not any(k.startswith("pt.req:") for k in ev)


# --------------------------------------------------------------------------- #
# stalls: the judge, its counters, the slow round's record and spans
# --------------------------------------------------------------------------- #


def _watch(*wheres):
    from picotron_tpu.obs import StallWatch

    watch = StallWatch(MetricsRegistry(), SpanTracer(ring=64))
    watch.register(*wheres)
    return watch


def test_judge_gives_no_verdict_before_eight_predecessors():
    watch = _watch("step/sync")
    for _ in range(stalls_mod.MIN_HISTORY - 1):
        assert watch.judge("step/sync", "decode", 0.05) is None
    assert watch.judge("step/sync", "decode", 5.0) is None  # the eighth
    # ... and is now a predecessor itself: the median of eight holds
    assert watch.judge("step/sync", "decode", 5.0) == pytest.approx(
        (0.05, 4.95))


@pytest.mark.parametrize("reference,seconds,slow", [
    (0.001, 0.09, False),   # 90 x the reference, under 0.1 s over it
    (0.2, 0.5, False),      # 0.3 s over it, under 3 x
    (0.2, 0.601, True),     # past 3 x and 0.4 s over
    (0.001, 0.102, True),   # past both by a hair
])
def test_judge_needs_the_ratio_and_the_excess(reference, seconds, slow):
    watch = _watch("step/plan")
    for _ in range(stalls_mod.MIN_HISTORY):
        watch.judge("step/plan", "", reference)
    assert (watch.judge("step/plan", "", seconds) is not None) == slow


def test_judge_excess_is_duration_less_the_median():
    watch = _watch("step/deliver")
    for s in (0.01, 0.02, 0.03, 0.04, 0.05, 0.06, 0.07, 0.08):
        assert watch.judge("step/deliver", "", s) is None
    ref, excess = watch.judge("step/deliver", "", 9.0)
    assert ref == pytest.approx(0.045) and excess == pytest.approx(8.955)
    # the median of the nine predecessors is 0.05 whatever the 9.0 among them
    ref, excess = watch.judge("step/deliver", "", 1.0)
    assert ref == pytest.approx(0.05) and excess == pytest.approx(0.95)


def test_judge_two_keys_do_not_share_a_reference():
    watch = _watch("step/admit")
    for _ in range(10):
        watch.judge("step/admit", "none", 0.0001)
        watch.judge("step/admit", "8x4096", 0.9)
    # a long admission among long admissions is no stall; among none it is
    assert watch.judge("step/admit", "8x4096", 1.1) is None
    assert watch.judge("step/admit", "none", 1.1) is not None
    assert stalls_mod.admit_key(0, 0) == "none"
    assert stalls_mod.admit_key(1, 3) == "1x16"
    assert stalls_mod.admit_key(3, 584) == "4x1024"
    assert stalls_mod.admit_key(16, 8192) == "16x8192"


def test_judge_null_form_records_nothing():
    off = Obs(enabled=False)
    off.stalls.register("step/sync")
    off.stalls.register_watchdog()
    off.stalls.oversleep(3.0)
    for _ in range(12):
        assert off.stalls.judge("step/sync", "decode", 0.01) is None
    assert off.stalls.judge("step/sync", "decode", 9.0) is None
    with off.phase("loop/results"), off.part("sync/wait"):
        pass
    assert off.stalls.stats() == {} and off.stalls.take_slow() == []
    assert off.registry.prometheus() == ""


def _scripted_batcher(monkeypatch, sync_costs, slots=2, **inf):
    """A batcher on a manual clock whose n-th wait for the device costs
    ``sync_costs(n)`` seconds and whose other phases cost a little."""
    cfg, engine, params = _engine(slots=slots, decode_block_len=2, **inf)
    clock = _ManualClock()
    engine.obs = Obs(enabled=True, registry=MetricsRegistry(),
                     tracer=SpanTracer(ring=4096, clock=clock))
    b = ContinuousBatcher(engine, params, clock=clock)
    waits = [0]
    ready = jax.block_until_ready

    def waiting(x):
        waits[0] += 1
        clock.t += sync_costs(waits[0])
        return ready(x)

    monkeypatch.setattr(jax, "block_until_ready", waiting)
    inner = engine.decode_block

    def issuing(*a, **kw):
        clock.t += 3e-3
        return inner(*a, **kw)

    monkeypatch.setattr(engine, "decode_block", issuing)
    return engine, b, clock


@pytest.mark.parametrize("overlap", [False, True])
def test_one_slow_sync_is_counted_recorded_and_pinned(overlap, monkeypatch):
    """Round 12's wait for the device takes 2 s where the others take 5 ms:
    the excess over the median lands in the two counters under
    ``step/sync``, nowhere else, and ``stats()['stalls']`` holds the round
    with its phases, its parts and the host's evidence."""
    engine, b, clock = _scripted_batcher(
        monkeypatch, lambda n: 2.0 if n == 12 else 5e-3, overlap=overlap)
    b.submit(Request("p", [3, 5, 7], max_new_tokens=41))
    b.run()
    snap = parse_prometheus(engine.obs.registry.prometheus())
    assert snap['picotron_stall_seconds_total{where="step/sync"}'] \
        == pytest.approx(2.0 - 5e-3)
    assert snap['picotron_stalls_total{where="step/sync"}'] == 1
    for where in ("step/plan", "step/admit", "step/issue", "step/deliver"):
        assert snap[f'picotron_stall_seconds_total{{where="{where}"}}'] == 0
        assert snap[f'picotron_stalls_total{{where="{where}"}}'] == 0
    st = b.stats()["stalls"]
    assert st["count"]["step/sync"] == 1
    assert st["seconds"]["step/sync"] == pytest.approx(1.995)
    (rec,) = st["slowest"]
    assert (rec["where"], rec["key"]) == ("step/sync", "decode")
    assert rec["round"] == (13 if overlap else 12)  # drained a round later
    assert rec["duration_s"] == pytest.approx(2.0)
    assert rec["reference_s"] == pytest.approx(5e-3)
    assert rec["excess_s"] == pytest.approx(1.995)
    # the part that holds the slow sync names the device wait
    assert rec["parts"]["sync/wait"] == pytest.approx(2.0)
    assert rec["parts"]["sync/fetch"] == 0.0
    assert rec["phases"]["step/sync"] == pytest.approx(2.0)
    assert rec["phases"]["step/issue"] == pytest.approx(3e-3)
    assert rec["live_slots"] == 1 and rec["prompt_tokens"] == 0
    assert rec["t0"] <= clock.t and rec["unix_t0"] > 1e9
    assert set(rec["host"]) == {
        "wall_s", "thread_cpu_s", "process_cpu_s", "involuntary_switches",
        "major_faults", "gc_s", "oversleep_s"}
    assert rec["host"]["oversleep_s"] == 0.0
    # the record waits for whoever logs it, once
    assert engine.obs.stalls.take_slow() == [rec]
    assert engine.obs.stalls.take_slow() == []
    # the round's spans are pinned under its number
    rounds = trace_dump.stall_rounds(engine.obs.tracer.chrome_trace())
    names = {e["name"] for e in rounds[rec["round"]]}
    assert {"step/sync", "sync/wait", "sync/fetch", "step/deliver",
            "dispatch/decode", "decode"} <= names


def test_admissions_of_mixed_prompt_lengths_are_no_stall(monkeypatch):
    """A prefill costs 20 ms a row of its padded bucket here, so a round
    that admits 60 tokens takes 1.28 s in ``step/admit`` and one that
    admits 4 takes 0.32: four times and a second apart, and no stall,
    because each is held against admissions of its own size."""
    engine, b, clock = _scripted_batcher(monkeypatch, lambda n: 5e-3,
                                         slots=1)
    inner = b._prefill_into

    def prefilling(req, i, key=None):
        clock.t += 0.02 * engine.prefill_bucket(len(req.prompt))
        return inner(req, i, key)

    monkeypatch.setattr(b, "_prefill_into", prefilling)
    lengths = [4, 60, 20, 12, 40, 30]
    for i in range(60):
        n = lengths[i % len(lengths)]
        b.submit(Request(f"m{i}", list(range(3, 3 + n)), max_new_tokens=3))
    b.run()
    snap = parse_prometheus(engine.obs.registry.prometheus())
    assert snap['picotron_stall_seconds_total{where="step/admit"}'] == 0
    assert b.stats()["stalls"]["slowest"] == []
    # not for want of a verdict: the keys filled, and apart
    history = {k[1]: list(v) for k, v in engine.obs.stalls._history.items()
               if k[0] == "step/admit"}
    assert {"1x16", "1x32", "1x64"} <= set(history)
    assert all(len(history[k]) > stalls_mod.MIN_HISTORY
               for k in ("1x16", "1x32", "1x64"))
    assert min(history["1x64"]) > 3 * max(history["1x16"])
    # ... and one key for them all would have cried stall
    flat = _watch("step/admit")
    assert any(flat.judge("step/admit", "", s)
               for s in history["1x16"][:8] + history["1x64"][:1])


def test_pinned_spans_survive_the_ring_turning_over(tmp_path):
    clock = _ManualClock()
    tr = SpanTracer(ring=8, clock=clock)
    root = tr.begin("request", uid="u")
    for name in ("step/issue", "step/sync", "step/deliver"):
        with tr.span(name):
            clock.t += 0.5
    tr.record("dispatch/decode", 100.0, 101.0)
    tr.record("decode", 100.0, 101.0, parent=root)
    assert tr.pin(100.0, clock.t, stall_round=7, stall_where="step/sync") == 5
    for i in range(20):  # the ring of 8 turns over twice and more
        with tr.span(f"later{i}"):
            clock.t += 0.01
    assert not any(s.name == "step/sync" for s in tr.spans())
    trace = tr.chrome_trace()
    assert trace_dump.validate(trace) == []
    assert len(trace["traceEvents"]) == 8 + 5  # each span once
    (events,) = trace_dump.stall_rounds(trace).values()
    assert [e["name"] for e in events if e["name"].startswith("step/")] \
        == ["step/issue", "step/sync", "step/deliver"]
    assert all(e["args"]["stall_where"] == "step/sync" for e in events)
    # a span pinned and still in the ring is rendered once, with its label
    tr.pin(clock.t - 0.005, clock.t, stall_round=8, stall_where="step/plan")
    trace = tr.chrome_trace()
    assert len(trace["traceEvents"]) == 8 + 5
    assert len(trace_dump.stall_rounds(trace)[8]) == 1
    # the tool finds the round in a dump
    path = tmp_path / "trace.json"
    tr.dump_chrome(str(path))
    assert trace_dump.main([str(path), "--stall-round", "7"]) == 0
    assert trace_dump.main([str(path), "--stall-round", "9"]) == 1
    # no more than PINNED_ROUNDS windows are kept
    for i in range(40):
        tr.pin(0.0, 0.0, stall_round=100 + i, stall_where="x")
    assert len(tr.pinned()) == tracing_mod.PINNED_ROUNDS


def _front(**kw):
    from picotron_tpu.tools import serve

    cfg, engine, params = _engine(slots=2)
    kw.setdefault("log", lambda *a, **k: None)
    return engine, serve.FrontEnd(engine, params, **kw)


def test_watchdog_counts_how_far_its_sleep_overran():
    engine, front = _front(watchdog_poll_s=0.25)
    clock = _ManualClock()

    def sleep(overrun):
        def sleeping(s):
            clock.t += s + overrun
        return sleeping

    front._nap(sleep=sleep(0.0), clock=clock)
    front._nap(sleep=sleep(2.8), clock=clock)
    front._nap(sleep=sleep(0.002), clock=clock)  # the scheduler's: no freeze
    front._nap(sleep=sleep(0.011), clock=clock)
    snap = parse_prometheus(front.metrics_text())
    assert snap["picotron_watchdog_oversleep_seconds_total"] \
        == pytest.approx(2.811)
    assert front.stats()["stalls"]["oversleep_s"] == pytest.approx(2.811)
    assert front.stats()["stalls"]["watchdog_episodes"] == 0


def test_every_stall_family_prints_at_zero_before_any_round():
    engine, front = _front()
    snap = parse_prometheus(front.metrics_text())
    for where in ("loop/lock_wait", "step/plan", "step/admit", "step/issue",
                  "step/sync", "step/deliver", "loop/results"):
        assert snap[f'picotron_stall_seconds_total{{where="{where}"}}'] == 0
        assert snap[f'picotron_stalls_total{{where="{where}"}}'] == 0
    assert not any("loop/idle" in k for k in snap if "stall" in k)
    assert snap["picotron_watchdog_oversleep_seconds_total"] == 0
    for g in "012":  # the process's registry; a collection may have run
        assert snap[
            f'picotron_gc_pause_seconds_total{{generation="{g}"}}'] >= 0
    assert front.stats()["stalls"]["slowest"] == []


def test_gc_pauses_are_counted_by_generation():
    import gc

    c = GLOBAL_REGISTRY.counter("picotron_gc_pause_seconds_total",
                                generation="2")
    obs_mod.install_gc_pause_counter(GLOBAL_REGISTRY)  # again: once only
    before, callbacks = c.value, len(gc.callbacks)
    gc.collect()
    assert c.value > before and len(gc.callbacks) == callbacks


def test_a_slow_results_phase_of_the_loop_is_logged_when_it_happens(
        monkeypatch):
    """The front end's own phases are judged too: a ``loop/results`` that
    takes 1.5 s (a log that blocked) is a ``slow_interval`` event in the
    log at once, and ``/statz`` has it."""
    from picotron_tpu.tools import serve

    cfg, engine, params = _engine(slots=2, decode_block_len=2)
    clock = _ManualClock()
    engine.obs = Obs(enabled=True, registry=MetricsRegistry(),
                     tracer=SpanTracer(ring=4096, clock=clock))
    lines = []
    srv = serve.Server(engine, params, port=0,
                       log=lambda m, **k: lines.append(json.loads(m)))
    takes = [0]
    inner = srv.front._batcher.take_results

    def taking():
        takes[0] += 1
        clock.t += 1.5 if takes[0] == 12 else 1e-3
        return inner()

    monkeypatch.setattr(srv.front._batcher, "take_results", taking)
    srv.start()
    try:
        st, body = serve._post(srv.port, {"prompt": [1, 2, 3],
                                          "max_new_tokens": 41})
        assert st == 200 and len(body["tokens"]) == 41
        deadline = time.monotonic() + 30
        while not any(e["evt"] == "slow_interval" for e in lines) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        (evt,) = [e for e in lines if e["evt"] == "slow_interval"]
        assert evt["where"] == "loop/results" and evt["round"] == 11
        assert evt["excess_s"] == pytest.approx(1.499)
        assert evt["phases"] == {"loop/results": 1.5}
        st, stats = serve._get(srv.port, "/statz")
        assert stats["stalls"]["slowest"][0]["where"] == "loop/results"
        assert stats["stalls"]["seconds"]["loop/results"] \
            == pytest.approx(1.499)
        st, trace = serve._get(srv.port, "/tracez")
        assert 11 in {int(k) for k in trace_dump.stall_rounds(trace)}
    finally:
        srv.drain_and_join(timeout=30)


_READERS = {
    "batcher.stall_s": 2.25, "batcher.stall_s.chat": 2.25,
    "engine.device_wait_stall_s": 2.0,
    "engine.device_wait_stall_s.chat": 2.0,
    "front.oversleep_s": 1.5, "front.oversleep_s.chat": 1.5,
}


@pytest.mark.parametrize("name", sorted(_READERS))
def test_stall_readers_none_without_the_family_zero_when_quiet(name):
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    try:
        from benchmarks import common
        read = common.load_file("layer_metrics", name).read
    finally:
        sys.path.remove(root)
    _, front = _front()
    quiet = front.metrics_text()
    # the parent's scrape: every other family, none of the judge's
    parent = "\n".join(line for line in quiet.splitlines()
                       if "picotron_stall" not in line
                       and "oversleep" not in line)
    assert read({"metrics_before": parent, "metrics_after": parent}) is None
    assert read({}) is None
    assert read({"metrics_before": quiet, "metrics_after": quiet}) == 0.0
    reg = front.obs.registry
    reg.counter("picotron_stall_seconds_total", where="step/sync").inc(1.25)
    reg.counter("picotron_stall_seconds_total", where="step/admit").inc(0.75)
    reg.counter("picotron_stall_seconds_total", where="step/plan").inc(0.25)
    front.obs.stalls.oversleep(1.5)
    got = read({"metrics_before": quiet,
                "metrics_after": front.metrics_text()})
    assert got == pytest.approx(_READERS[name])
