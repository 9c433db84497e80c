"""Serving resilience suite (ISSUE 6; docs/SERVING.md).

The serving stack's fault surfaces, each with a deterministic chaos
trigger and a bit-for-bit oracle where one exists:

- the sampler's non-finite gate (greedy over a sanitized distribution);
- dispatch retry (a transient exception costs nothing — outputs equal a
  fault-free run exactly);
- slot-failure isolation (a persistently failing slot finishes "error";
  SURVIVING slots' outputs are bit-identical to a fault-free run; no slot
  or queue entry leaks);
- flash->dense graceful degradation (process-wide, logged once,
  generation equals a dense engine's bit-for-bit);
- the HTTP front end (tools/serve.py): admission control (bounded queue
  503, token budget 429, Retry-After), streaming, SIGTERM-style drain
  with shed accounting, the stall watchdog, /healthz //readyz //statz;
- the serve-chaos acceptance: dispatch-exception + latency-spike +
  poisoned-logits faults in one run — no hangs, every submitted request
  terminates with an accounted finish_reason, unaffected requests
  bit-identical to a chaos-off run.

``make serve-chaos-smoke`` runs exactly this file.
"""

import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_config
from picotron_tpu.inference import (
    ContinuousBatcher,
    InferenceEngine,
    Request,
    sampling,
)
from picotron_tpu.models import llama
from picotron_tpu.resilience.chaos import ChaosError, ServingChaos
from picotron_tpu.tools import serve

MAX_LEN = 64


def _res(**kw):
    """A ResilienceConfig with serving-chaos overrides."""
    cfg = make_config(dict(_TINY))
    for k, v in kw.items():
        setattr(cfg.resilience, k, v)
    cfg.validate()
    return cfg.resilience


_TINY = dict(
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    hidden_size=32, intermediate_size=64, vocab_size=128,
    max_position_embeddings=MAX_LEN, rope_theta=10000.0, dtype="float32",
    attention_impl="sdpa")


def _engine(slots=3, hooks=None, **inf):
    cfg = make_config(dict(_TINY), seq=32)
    for k, v in inf.items():
        setattr(cfg.inference, k, v)
    engine = InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN,
                             hooks=hooks)
    params = engine.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    return cfg, engine, params


def _requests(n=3, temperature=0.0, max_new=8):
    # even-indexed requests carry the stochastic sampling params, so in the
    # isolation test (slot 1 faulted) the SURVIVORS include a sampled row
    return [Request(f"q{i}", [3 + i, 7 + i, 11 + i], max_new_tokens=max_new,
                    temperature=0.0 if i % 2 else temperature)
            for i in range(n)]


# --------------------------------------------------------------------------- #
# sampler non-finite gate
# --------------------------------------------------------------------------- #


def test_sampler_nonfinite_gate_greedy_over_sanitized():
    """Rows with non-finite logits emit the argmax of the FINITE entries
    (token 0 when nothing survives) on both the greedy and the stochastic
    path; finite rows are bit-identical to the ungated sampler."""
    V = 16
    logits = np.zeros((4, V), np.float32)
    logits[0, 5] = 3.0                      # finite row
    logits[1, 7] = 2.0
    logits[1, 9] = np.nan                   # partially poisoned
    logits[2, :] = np.nan                   # fully poisoned
    logits[3, 11] = np.inf                  # +inf: also non-finite
    logits[3, 4] = 2.0                      # ...the finite max beneath it
    key = jax.random.PRNGKey(0)

    for temp in (0.0, 0.7):
        t = np.full(4, temp, np.float32)
        toks = np.asarray(sampling.sample(
            jnp.asarray(logits), key, t, np.zeros(4, np.int32),
            np.ones(4, np.float32)))
        assert toks[1] == 7      # NaN masked; argmax of the finite rest
        assert toks[2] == 0      # whole row bad -> the defined fallback
        assert toks[3] == 4      # inf masked; 4 is the finite max
        assert 0 <= toks[0] < V
    # finite-only input: the gate is the identity (greedy chain unchanged)
    clean = logits[:1]
    a = sampling.sample(jnp.asarray(clean), key, np.zeros(1, np.float32),
                        np.zeros(1, np.int32), np.ones(1, np.float32))
    assert int(a[0]) == 5


def test_poisoned_logits_round_emits_defined_tokens():
    """chaos_poison_logits_round: the poisoned dispatch's tokens are
    defined (the gate's greedy fallback), generation continues, and the
    request terminates normally — NaN never reaches the emitted stream."""
    chaos = ServingChaos(_res(chaos_poison_logits_round=2))
    cfg, engine, params = _engine(slots=2, hooks=chaos, decode_block_len=2)
    res = ContinuousBatcher(engine, params).run(_requests(2, max_new=10))
    for r in res.values():
        assert r.finish_reason == "length"
        assert len(r.tokens) == 10
        assert all(0 <= t < cfg.model.vocab_size for t in r.tokens)
    assert chaos.round >= 2  # the poison round actually ran
    assert "poison" in chaos._fired


def test_poisoned_verify_round_emits_defined_tokens():
    """On a speculative engine the poison round lands on a VERIFY
    dispatch: speculative_accept's sanitized argmax keeps the emitted
    stream defined and generation terminates normally."""
    chaos = ServingChaos(_res(chaos_poison_logits_round=2))
    cfg, engine, params = _engine(slots=2, hooks=chaos, spec_len=4)
    res = ContinuousBatcher(engine, params).run(_requests(2, max_new=10))
    for r in res.values():
        assert r.finish_reason == "length"
        assert len(r.tokens) == 10
        assert all(0 <= t < cfg.model.vocab_size for t in r.tokens)
    assert chaos.round >= 2
    # the knob actually fired on the verify path (it was silently a no-op
    # for spec engines before verify() consulted the poison hook)
    assert "poison" in chaos._fired


# --------------------------------------------------------------------------- #
# dispatch retry + slot isolation
# --------------------------------------------------------------------------- #


def test_transient_dispatch_exception_is_retried_bit_identical():
    """One injected dispatch exception (chaos_dispatch_raise_round) is
    absorbed by the retry: every output equals the fault-free run exactly
    — including the sampled (temperature > 0) streams, because the round's
    keys are drawn before the dispatch and reused by the retry."""
    reqs = _requests(3, temperature=0.8, max_new=20)  # >= 3 decode rounds
    _, e0, p0 = _engine()
    clean = ContinuousBatcher(e0, p0, seed=5).run(
        [Request(**vars(r)) for r in reqs])

    chaos = ServingChaos(_res(chaos_dispatch_raise_round=2))
    _, e1, p1 = _engine(hooks=chaos)
    b = ContinuousBatcher(e1, p1, seed=5)
    res = b.run([Request(**vars(r)) for r in reqs])

    assert chaos.round >= 2
    for uid in clean:
        assert res[uid].tokens == clean[uid].tokens
        assert res[uid].finish_reason == clean[uid].finish_reason
    assert b.counters["errored"] == 0
    assert b.counters["completed"] == 3


def test_slot_failure_isolation_mid_decode_block():
    """A slot whose dispatches persistently fail
    (chaos_dispatch_fail_slot) finishes "error"; SURVIVING slots' outputs
    are bit-identical to a fault-free run (greedy AND sampled rows); no
    slot or queue entry leaks."""
    reqs = _requests(3, temperature=0.8, max_new=10)
    _, e0, p0 = _engine()
    clean = ContinuousBatcher(e0, p0, seed=7).run(
        [Request(**vars(r)) for r in reqs])

    chaos = ServingChaos(_res(chaos_dispatch_fail_slot=1))
    _, e1, p1 = _engine(hooks=chaos)
    b = ContinuousBatcher(e1, p1, seed=7)
    res = b.run([Request(**vars(r)) for r in reqs])

    # q1 was admitted into slot 1: it errors with only its prefill-time
    # first token (identical to the clean run's first token)
    assert res["q1"].finish_reason == "error"
    assert res["q1"].tokens == clean["q1"].tokens[:1]
    # survivors: bit-identical streams
    for uid in ("q0", "q2"):
        assert res[uid].finish_reason == clean[uid].finish_reason
        assert res[uid].tokens == clean[uid].tokens
    # no leaks: every slot free, nothing queued, cache lengths zeroed,
    # and the accounting adds up
    assert all(s is None for s in b._slots)
    assert b.queue_depth == 0
    np.testing.assert_array_equal(np.asarray(b._cache["lengths"]), 0)
    assert b.counters["errored"] == 1
    assert b.counters["completed"] == 2
    assert b.counters["admitted"] == 3


def test_prefill_failure_costs_only_the_incoming_request():
    """A persistently failing prefill finishes ONLY the request being
    admitted ("error"); everyone already decoding — and everyone admitted
    after — is untouched (greedy oracle: identical tokens)."""

    class PrefillBomb:
        """Fails the 2nd prefill dispatch persistently (both attempts)."""

        def __init__(self):
            self.calls = 0

        def before_dispatch(self, kind, slots):
            if kind != "prefill":
                return
            self.calls += 1
            if self.calls in (2, 3):  # attempt + its retry
                raise ChaosError("prefill bomb")

        def poison_logits(self, kind):
            return False

    reqs = _requests(3, max_new=6)
    _, e0, p0 = _engine(slots=2)
    clean = ContinuousBatcher(e0, p0).run(
        [Request(**vars(r)) for r in reqs])

    _, e1, p1 = _engine(slots=2, hooks=PrefillBomb())
    b = ContinuousBatcher(e1, p1)
    res = b.run([Request(**vars(r)) for r in reqs])

    assert res["q1"].finish_reason == "error" and res["q1"].tokens == []
    for uid in ("q0", "q2"):
        assert res[uid].tokens == clean[uid].tokens
        assert res[uid].finish_reason == clean[uid].finish_reason
    assert all(s is None for s in b._slots) and b.queue_depth == 0
    assert b.counters == {"admitted": 3, "completed": 2, "expired": 0,
                          "errored": 1, "shed": 0}


def test_batcher_stats_counters_and_percentiles():
    _, engine, params = _engine(slots=2)
    b = ContinuousBatcher(engine, params)
    b.run(_requests(3, max_new=4))
    s = b.stats()
    assert s["admitted"] == s["completed"] == 3
    assert s["queued"] == 0 and s["active_slots"] == 0
    assert s["queue_wait_s"]["n"] == 3 and s["ttft_s"]["n"] == 3
    assert s["ttft_s"]["p50"] >= s["queue_wait_s"]["p50"] >= 0.0
    assert s["generated_tokens"] == 12


def test_batcher_rejects_duplicate_uid():
    """A duplicate uid would silently overwrite the first request's
    result and its queue-wait clock: fail at submission like the other
    contract violations. Once the result is taken, the uid is reusable."""
    _, engine, params = _engine(slots=2)
    b = ContinuousBatcher(engine, params)
    b.submit(Request("dup", [1, 2], max_new_tokens=2))
    with pytest.raises(ValueError, match="duplicate uid"):
        b.submit(Request("dup", [3, 4], max_new_tokens=2))
    res = b.run()
    assert res["dup"].finish_reason == "length"
    res2 = b.run([Request("dup", [5, 6], max_new_tokens=2)])
    assert res2["dup"].finish_reason == "length"


# --------------------------------------------------------------------------- #
# flash -> dense graceful degradation
# --------------------------------------------------------------------------- #


def test_flash_failure_falls_back_to_dense_for_the_process(
        monkeypatch, capsys):
    import picotron_tpu.inference.engine as eng_mod
    import picotron_tpu.ops.pallas.decode_attention as da

    monkeypatch.setattr(eng_mod, "_FLASH_BROKEN", False)

    def kaput(*a, **kw):
        raise RuntimeError("flash kernel kaput")

    monkeypatch.setattr(da, "flash_decode_attention", kaput)

    reqs = _requests(2, max_new=6)
    _, e0, p0 = _engine(slots=2)  # dense oracle
    clean = ContinuousBatcher(e0, p0).run(
        [Request(**vars(r)) for r in reqs])

    _, e1, p1 = _engine(slots=2, attend_impl="flash")
    assert e1.attend_impl == "flash"
    res = ContinuousBatcher(e1, p1).run(
        [Request(**vars(r)) for r in reqs])
    # degraded transparently: same results as a dense engine, flipped impl
    assert e1.attend_impl == "dense"
    for uid in clean:
        assert res[uid].tokens == clean[uid].tokens
    out = capsys.readouterr().out
    assert out.count("falling back to 'dense'") == 1
    # the latch is process-wide: a NEW flash engine starts on dense
    assert eng_mod._FLASH_BROKEN
    _, e2, _ = _engine(slots=2, attend_impl="flash")
    assert e2.attend_impl == "dense"
    # with the fallback disabled there is no silent degradation: the
    # failure lands in the batcher's slot recovery instead (requests
    # error, the engine stays on flash, the process survives)
    monkeypatch.setattr(eng_mod, "_FLASH_BROKEN", False)
    _, e3, p3 = _engine(slots=2, attend_impl="flash",
                        attend_fallback=False)
    res3 = ContinuousBatcher(e3, p3).run(
        [Request("x", [1, 2], max_new_tokens=2)])
    assert res3["x"].finish_reason == "error"
    assert e3.attend_impl == "flash"


# --------------------------------------------------------------------------- #
# HTTP front end
# --------------------------------------------------------------------------- #


def _server(slots=2, hooks=None, inf=(), **front_kw):
    cfg, engine, params = _engine(slots=slots, hooks=hooks, **dict(inf))
    front_kw.setdefault("log", lambda *a, **k: None)
    srv = serve.Server(engine, params, port=0, **front_kw)
    srv.start()
    return cfg, srv


def test_http_generate_stream_health_and_stats():
    cfg, srv = _server()
    try:
        port = srv.port
        assert serve._get(port, "/healthz")[0] == 200
        assert serve._get(port, "/readyz")[0] == 200

        spec = {"prompt": [1, 2, 3], "max_new_tokens": 6}
        st, body = serve._post(port, spec)
        assert st == 200 and body["finish_reason"] == "length"
        assert len(body["tokens"]) == 6
        assert body["queue_wait_s"] is not None

        st, events = serve._post(port, {**spec, "stream": True},
                                 stream=True)
        assert st == 200
        toks = [e["token"] for e in events if e["event"] == "token"]
        done = [e for e in events if e["event"] == "done"]
        assert len(done) == 1 and done[0]["tokens"] == toks
        assert toks == body["tokens"]  # greedy: deterministic across posts

        st, stats = serve._get(port, "/statz")
        assert st == 200
        assert stats["completed"] == stats["admitted"] == 2
        assert stats["rejected"] == {"queue_full": 0, "token_budget": 0,
                                     "page_budget": 0, "draining": 0,
                                     "stalled": 0, "dead": 0, "role": 0,
                                     "tenant_quota": 0}
        assert not stats["draining"] and not stats["stalled"]
    finally:
        srv.drain_and_join(timeout=60)


def _poll_statz(port, cond, deadline_s=10.0):
    """Poll /statz until ``cond(stats)`` holds (returns the stats) or the
    deadline passes (raises)."""
    deadline = time.monotonic() + deadline_s
    while True:
        stats = serve._get(port, "/statz")[1]
        if cond(stats):
            return stats
        if time.monotonic() > deadline:
            raise AssertionError(f"statz condition never held: {stats}")
        time.sleep(0.01)


class _SlowDecode:
    """Engine hooks that say when a request decodes and keep it decoding
    (50 ms a dispatch). For tests that need a request in flight: polling
    /statz for ``active_slots > 0`` raced it, because the loop holds the
    front end's lock through each step and ``stats()`` then answers with a
    partial snapshot, so on a busy host the 24-40 tokens could be done
    before one whole snapshot came back, and the condition never held."""

    def __init__(self):
        self.decoding = threading.Event()

    def before_dispatch(self, kind, slots):
        if kind == "decode":
            self.decoding.set()
            time.sleep(0.05)

    def poison_logits(self, kind):
        return False

    def wait(self):
        assert self.decoding.wait(60), "the request never reached decode"


def test_http_admission_bounds_shed_with_retry_after():
    # token budget first: one live request exhausts it. The slow request
    # runs per-token (block 1) with a big budget, so it is live for many
    # lock-release windows; its COMMITMENT counts from submission (queued
    # or slotted), so the second POST is over budget the moment /statz
    # shows the first one live.
    cfg, srv = _server(token_budget=70, max_queue=8,
                       inf={"decode_block_len": 1})
    try:
        port = srv.port
        results = {}

        def bg(name, spec):
            results[name] = serve._post(port, spec)

        t = threading.Thread(target=bg, args=(
            "a", {"prompt": [1, 2, 3], "max_new_tokens": 58,
                  "uid": "slow"}))
        t.start()  # cost 61 of 70
        # .get: while the first dispatch compiles, /statz may answer with
        # the degraded (lock-free) snapshot, which has no counters
        _poll_statz(port,
                    lambda s: s.get("admitted", 0) + s.get("queued", 0) >= 1)
        st, body = serve._post(port, {"prompt": [5, 6, 7],
                                      "max_new_tokens": 8})  # cost 11
        assert st == 429 and body["shed"]
        t.join(60)
        assert results["a"][0] == 200
        st, stats = serve._get(port, "/statz")
        assert stats["rejected"]["token_budget"] == 1
    finally:
        srv.drain_and_join(timeout=60)

    # bounded wait queue: depth 0 sheds every submission outright
    cfg, srv = _server(max_queue=0)
    try:
        st, body = serve._post(srv.port, {"prompt": [1], "max_new_tokens": 2})
        assert st == 503 and body["shed"]
        assert serve._get(srv.port, "/statz")[1]["rejected"]["queue_full"] == 1
    finally:
        srv.drain_and_join(timeout=60)


def test_oversized_budget_is_window_capped_not_rejected():
    """A max_new_tokens beyond the sequence window admits at its real
    (window-capped) commitment instead of 429ing forever — the batcher
    can only ever generate max_seq_len - len(prompt) tokens, so that is
    what admission charges against the token budget."""
    cfg, srv = _server()
    try:
        st, body = serve._post(srv.port, {"prompt": [1, 2, 3],
                                          "max_new_tokens": 100000})
        assert st == 200 and body["finish_reason"] == "length"
        assert len(body["tokens"]) == MAX_LEN - 3
    finally:
        srv.drain_and_join(timeout=60)


def test_stalled_rejections_count_under_their_own_lock():
    """The "stalled" rejection fires exactly when ``_mu`` could NOT be
    acquired, so the counter cannot be guarded by ``_mu`` — a dedicated
    leaf lock (``_rej_mu``) guards every increment (picolint PICO-C003:
    concurrent timed-out handlers were doing an unlocked read-modify-
    write and losing updates). N handlers shedding concurrently against
    a wedged dispatch must count exactly N."""
    cfg, engine, params = _engine(slots=1)
    front = serve.FrontEnd(engine, params, log=lambda *a, **k: None)

    class _Wedged:  # a dispatch holding _mu forever: timed acquires fail
        def acquire(self, timeout=None):
            return False

        def release(self):
            raise AssertionError("never acquired")

    front._mu = _Wedged()
    n, statuses = 16, []

    def handler():
        try:
            front.submit({"prompt": [1, 2], "max_new_tokens": 2})
        except serve.AdmissionError as e:
            statuses.append(e.status)

    threads = [threading.Thread(target=handler) for _ in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert statuses == [503] * n
    assert front.rejections["stalled"] == n
    # stats() snapshots the counters under the same leaf lock (and takes
    # the degraded no-_mu path here, like an operator mid-stall)
    assert front.stats()["rejected"]["stalled"] == n


def test_waiter_maps_only_mutated_under_mu():
    """``_deliver`` pops ``_req_t``/``_waiters`` under ``_mu`` (picolint
    PICO-C003): the dispatch thread used to pop them unlocked while
    handler threads insert them — and check duplicate uids against them
    — under the lock. Guarded dicts assert the lock is held at every
    mutation; an unlocked pop kills the dispatch loop, which the
    result/dead checks surface."""
    cfg, engine, params = _engine(slots=2)
    front = serve.FrontEnd(engine, params, log=lambda *a, **k: None)

    class _Guarded(dict):
        def __init__(self, lock):
            super().__init__()
            self._lock = lock

        def __setitem__(self, k, v):
            assert self._lock.locked(), "waiter-map mutation outside _mu"
            dict.__setitem__(self, k, v)

        def pop(self, *a):
            assert self._lock.locked(), "waiter-map mutation outside _mu"
            return dict.pop(self, *a)

    front._waiters = _Guarded(front._mu)
    front._req_t = _Guarded(front._mu)
    front.start()
    try:
        _, waiter = front.submit({"prompt": [1, 2, 3],
                                  "max_new_tokens": 4})
        toks, res = [], None
        while res is None:
            kind, payload = waiter.events.get(timeout=30)
            if kind == "done":
                res = payload
            else:
                toks.extend(payload)
        assert res.finish_reason == "length" and res.tokens == toks
        assert len(res.tokens) == 4
        assert not front.dead
    finally:
        front.begin_drain()
        front.join(timeout=30)
    assert not front._waiters and not front._req_t


def test_http_rejects_zero_budget_and_oversized_bodies():
    """max_new_tokens < 1 is a 400 at the door (a zero-budget request
    would hold a slot forever — no token ever completes it — and a
    negative one corrupts the token-budget arithmetic); a body whose
    declared Content-Length exceeds the cap is a 413 before any read."""
    import http.client

    cfg, srv = _server()
    try:
        port = srv.port
        for bad in (0, -3):
            st, body = serve._post(port, {"prompt": [1, 2],
                                          "max_new_tokens": bad})
            assert st == 400 and "max_new_tokens" in body["error"]
        # the batcher guards too: direct embedders get the same contract
        with pytest.raises(ValueError, match="max_new_tokens"):
            srv.front._batcher.submit(Request("z", [1], max_new_tokens=0))

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/generate", b"{}",
                     {"Content-Length": str(serve.MAX_BODY_BYTES + 1)})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        conn.close()
        assert resp.status == 413 and "too large" in body["error"]

        # a negative declared length is a malformed header: 400, not 413
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/generate", b"", {"Content-Length": "-5"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        conn.close()
        assert resp.status == 400 and "Content-Length" in body["error"]

        # nothing above was admitted; the server still serves
        st, body = serve._post(port, {"prompt": [1, 2],
                                      "max_new_tokens": 2})
        assert st == 200 and body["finish_reason"] == "length"
        stats = serve._get(port, "/statz")[1]
        assert stats["admitted"] == stats["completed"] == 1
    finally:
        srv.drain_and_join(timeout=60)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_http_submissions_after_loop_death_are_shed():
    """Once the dispatch loop dies on an unexpected exception, in-flight
    waiters get terminal "error" results (nobody hangs) and LATER
    submissions are shed with 503 instead of registering waiters no loop
    will ever complete."""
    cfg, srv = _server()
    try:
        port = srv.port

        def boom(*a, **k):
            raise RuntimeError("dispatch wedged beyond repair")

        srv.front._batcher.step = boom
        st, body = serve._post(port, {"prompt": [1, 2],
                                      "max_new_tokens": 4})
        assert st == 500 and body["finish_reason"] == "error"
        srv.front.join(timeout=60)
        assert srv.front.stopped.is_set()
        # death is a dedicated latch: the watchdog's recovery tick clears
        # `stalled` (progress looked recent), which must NOT flip a dead
        # server's healthz back to 200
        assert srv.front.dead
        time.sleep(3 * srv.front.watchdog_poll_s)
        assert not srv.front.healthy()  # supervisors see the 503
        assert not srv.front.ready()
        with pytest.raises(serve.AdmissionError) as ei:
            srv.front.submit({"prompt": [1, 2], "max_new_tokens": 4})
        assert ei.value.status == 503
        assert srv.front.rejections["dead"] == 1
        assert not srv.front._waiters  # nothing stranded
    finally:
        srv.drain_and_join(timeout=60)


def test_http_drain_finishes_inflight_and_sheds_queued():
    # token_budget above the default slots*max_seq_len: "b" must reach the
    # QUEUE (and be shed by the drain), not bounce off the budget gate
    cfg, srv = _server(slots=1, token_budget=256,
                       inf={"decode_block_len": 1})
    try:
        port = srv.port
        results = {}

        def bg(name, spec):
            results[name] = serve._post(port, spec)

        ta = threading.Thread(target=bg, args=(
            "a", {"prompt": [1, 2, 3], "max_new_tokens": 59}))
        ta.start()
        _poll_statz(port, lambda s: s.get("admitted", 0) >= 1)  # "a" slotted
        tb = threading.Thread(target=bg, args=(
            "b", {"prompt": [4, 5], "max_new_tokens": 4}))
        tb.start()
        # "b" can only wait in the queue (one slot, "a" decoding per-token)
        _poll_statz(port, lambda s: s.get("queued", 0) >= 1)
        srv.front.begin_drain()
        assert serve._get(port, "/readyz")[0] == 503
        ta.join(60)
        tb.join(60)
        # in-flight finished intact; queued-but-unstarted was shed
        assert results["a"][0] == 200
        assert results["a"][1]["finish_reason"] == "length"
        assert len(results["a"][1]["tokens"]) == 59
        assert results["b"][0] == 503
        assert results["b"][1]["finish_reason"] == "shed"
        # post-drain: submissions are rejected, the loop has exited
        srv.front.join(timeout=60)
        assert srv.front.stopped.is_set()
        stats = srv.front.stats()
        assert stats["shed"] == 1 and stats["completed"] >= 1
        assert stats["queued"] == 0 and stats["active_slots"] == 0
    finally:
        srv.drain_and_join(timeout=60)


def test_watchdog_flags_latency_stall_and_recovers():
    chaos = ServingChaos(_res(chaos_latency_round=2, chaos_latency_s=0.8))
    cfg, srv = _server(hooks=chaos, stall_timeout_s=0.15,
                       watchdog_poll_s=0.03)
    try:
        st, body = serve._post(srv.port, {"prompt": [1, 2, 3],
                                          "max_new_tokens": 16})
        assert st == 200 and len(body["tokens"]) == 16  # spike, no hang
        # the flag and its recovery are the watchdog thread's writes —
        # poll for both (its next tick clears `stalled` once steps resume)
        deadline = time.monotonic() + 5
        while (time.monotonic() < deadline
               and not (srv.front.stalls >= 1 and not srv.front.stalled)):
            time.sleep(0.02)
        assert srv.front.stalls >= 1     # the spike was flagged...
        assert not srv.front.stalled     # ...and recovery cleared it
        assert serve._get(srv.port, "/healthz")[0] == 200
    finally:
        srv.drain_and_join(timeout=60)


def test_readyz_distinguishes_draining_from_dead():
    """The readyz 503 body carries the POLLER'S contract (ISSUE 12): a
    router must stop placing on a draining replica without tripping its
    circuit breaker, and must treat a dead one as a failure — before the
    "state" field, both were indistinguishable 503s."""
    slow = _SlowDecode()
    cfg, srv = _server(slots=1, hooks=slow, inf={"decode_block_len": 1})
    try:
        port = srv.port
        st, body = serve._get(port, "/readyz")
        assert st == 200 and body["state"] == "ready"
        # hold the drain window open with an in-flight request, exactly
        # like a rolling restart catches a replica mid-generation
        results = {}

        def bg():
            results["slow"] = serve._post(port, {"prompt": [1, 2, 3],
                                                 "max_new_tokens": 40})

        t = threading.Thread(target=bg)
        t.start()
        slow.wait()
        srv.front.begin_drain()
        st, body = serve._get(port, "/readyz")
        assert st == 503
        assert body["state"] == "draining" and body["draining"]
        assert not body["dead"]
        t.join(60)
        assert results["slow"][0] == 200  # drain finished the in-flight
    finally:
        srv.drain_and_join(timeout=60)

    # dead flavor: the dispatch loop died -> "dead", not "draining".
    # Keep the listener up past the death (the serve CLI's window between
    # loop death and process exit) so the surface is observable.
    cfg, srv = _server()
    try:
        srv.front._on_drained = None

        def boom(*a, **k):
            raise RuntimeError("dispatch died")

        srv.front._batcher.step = boom
        st, body = serve._post(srv.port, {"prompt": [1], "max_new_tokens": 2})
        assert st == 500
        srv.front.join(timeout=60)
        st, body = serve._get(srv.port, "/readyz")
        assert st == 503 and body["state"] == "dead"
    finally:
        srv.drain_and_join(timeout=60)


def test_request_id_echoed_on_every_stream_row():
    """A client-supplied request_id rides every NDJSON token row, the
    done row, and the non-streaming document (falling back to the server
    uid) — the correlation key router-side replay dedup is audited by."""
    cfg, srv = _server()
    try:
        spec = {"prompt": [5, 6, 7], "max_new_tokens": 4,
                "request_id": "corr-77", "stream": True}
        st, events = serve._post(srv.port, spec, stream=True)
        assert st == 200 and len(events) == 5
        assert all(e["request_id"] == "corr-77" for e in events)
        st, body = serve._post(srv.port, {"prompt": [5, 6, 7],
                                          "max_new_tokens": 2,
                                          "request_id": "corr-78"})
        assert st == 200 and body["request_id"] == "corr-78"
        # no request_id -> the uid stands in, so the field is always there
        st, events = serve._post(srv.port, {"prompt": [5, 6], "uid": "u9",
                                            "max_new_tokens": 2,
                                            "stream": True}, stream=True)
        assert st == 200
        assert all(e["request_id"] == "u9" for e in events)
    finally:
        srv.drain_and_join(timeout=60)


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_killed_server_releases_streaming_waiters_with_error():
    """A replica killed mid-generation (dispatch loop dies, the
    in-process SIGKILL the router chaos drill uses) must release every
    in-flight STREAM with a terminal ``finish_reason: "error"`` done row
    — not strand the client — because that row is what triggers the
    router's failover replay."""
    from picotron_tpu.resilience.chaos import RouterChaos

    cfg, srv = _server(slots=2, inf={"decode_block_len": 1})
    try:
        port = srv.port
        rows = []
        got_some = threading.Event()

        def on_token(i, row):
            got_some.set()

        from picotron_tpu.tools.router import _stream_post

        def bg():
            rows.append(_stream_post(
                port, {"prompt": [3, 1, 4], "max_new_tokens": 48,
                       "request_id": "kill-1"}, on_token=on_token))

        t = threading.Thread(target=bg)
        t.start()
        assert got_some.wait(60)  # mid-generation, tokens flowing
        RouterChaos().kill(srv)
        t.join(60)
        assert not t.is_alive()  # the waiter was released, nobody hangs
        st, events = rows[0]
        done = [e for e in events if e.get("event") == "done"]
        assert len(done) == 1
        assert done[0]["finish_reason"] == "error"
        assert done[0]["request_id"] == "kill-1"
        assert srv.front.dead  # healthz tells the supervisor to restart
        assert not srv.front._waiters  # nothing stranded
    finally:
        srv.drain_and_join(timeout=60)


# --------------------------------------------------------------------------- #
# the serve-chaos acceptance: all three faults in one run
# --------------------------------------------------------------------------- #


def test_chaos_run_accounts_everything_and_spares_the_unaffected():
    """Dispatch-exception + latency-spike + poisoned-logits in one server:
    no hangs, every submitted request terminates with an accounted
    finish_reason, and requests that ran AFTER the fault window are
    bit-identical to a chaos-off run."""
    batch_a = [{"prompt": [2 + i, 9 + i], "max_new_tokens": 6,
                "uid": f"a{i}"} for i in range(3)]
    batch_b = [{"prompt": [30 + i, 40 + i, 50 + i], "max_new_tokens": 5,
                "uid": f"b{i}"} for i in range(3)]

    # chaos-off oracle for the unaffected batch (greedy: prompt-determined)
    cfg, srv = _server(slots=2, inf={"decode_block_len": 2})
    try:
        want_b = {s["uid"]: serve._post(srv.port, s)[1]["tokens"]
                  for s in batch_b}
    finally:
        srv.drain_and_join(timeout=60)

    chaos = ServingChaos(_res(
        chaos_dispatch_raise_round=2, chaos_latency_round=3,
        chaos_latency_s=0.1, chaos_poison_logits_round=4))
    cfg, srv = _server(slots=2, hooks=chaos, inf={"decode_block_len": 2})
    try:
        port = srv.port
        results = {}

        def bg(spec):
            results[spec["uid"]] = serve._post(port, spec)

        threads = [threading.Thread(target=bg, args=(s,)) for s in batch_a]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        # all three faults fired during batch A
        assert chaos._fired >= {"raise", "latency", "poison"}
        for s in batch_a:  # no hangs: every request terminated, accounted
            st, body = results[s["uid"]]
            assert st in (200, 500)
            assert body["finish_reason"] in ("eos", "length", "timeout",
                                             "shed", "error")
        # batch B runs after the fault window: bit-identical to chaos-off
        for s in batch_b:
            st, body = serve._post(port, s)
            assert st == 200
            assert body["tokens"] == want_b[s["uid"]]
        stats = srv.front.stats()
        terminal = (stats["completed"] + stats["expired"]
                    + stats["errored"])
        assert terminal == stats["admitted"] == 6
        assert stats["shed"] == 0 and stats["queued"] == 0
        assert stats["active_slots"] == 0
        assert serve._get(port, "/healthz")[0] == 200
    finally:
        srv.drain_and_join(timeout=60)


# --------------------------------------------------------------------------- #
# the fleet controller's drain protocol (ISSUE 17, tools/fleet.py)
# --------------------------------------------------------------------------- #


def _post_path(port, path, body=None):
    """POST an arbitrary path (serve._post is /generate-only)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("POST", path, json.dumps(body or {}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def _get_text(port, path):
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read().decode()
    finally:
        conn.close()


def test_http_drain_202_then_409_and_sigterm_races_one_drain():
    """The fleet drain protocol's worker half: POST /drain starts exactly
    one drain (202); a repeat is 409 "already draining"; a SIGTERM
    arriving DURING the HTTP drain (the PreemptionGuard loop calling
    begin_drain again — the controller sends both on purpose,
    belt-and-braces) must not double-run the drain — ``drain_begins``
    stays 1 and the loop exits clean, the serve CLI's exit-0 path."""
    slow = _SlowDecode()
    cfg, srv = _server(slots=1, hooks=slow, inf={"decode_block_len": 1})
    try:
        port = srv.port
        srv.front._on_drained = None  # keep the listener observable
        results = {}

        def bg():
            results["a"] = serve._post(port, {"prompt": [1, 2, 3],
                                              "max_new_tokens": 24})

        t = threading.Thread(target=bg)
        t.start()
        slow.wait()
        st, body = _post_path(port, "/drain")
        assert st == 202 and body["ok"] and body["state"] == "draining"
        st, body = _post_path(port, "/drain")
        assert st == 409 and "already draining" in body["error"]
        # the SIGTERM flavor of the same race, in-process: a second
        # begin_drain is a no-op, never a second drain
        assert srv.front.begin_drain() is False
        assert srv.front.drain_begins == 1
        t.join(60)
        assert results["a"][0] == 200  # in-flight finished intact
        srv.front.join(timeout=60)
        assert not srv.front.dead  # exit-0, not the crash path
        # the loop has exited: drain now reports the terminal state
        st, body = _post_path(port, "/drain")
        assert st == 409 and body["state"] in ("stopped", "dead")
        assert srv.front.drain_begins == 1
    finally:
        srv.drain_and_join(timeout=60)


def test_http_drain_on_dead_loop_is_409_dead():
    cfg, srv = _server()
    try:
        srv.front._on_drained = None

        def boom(*a, **k):
            raise RuntimeError("dispatch died")

        srv.front._batcher.step = boom
        st, _ = serve._post(srv.port, {"prompt": [1], "max_new_tokens": 2})
        assert st == 500
        srv.front.join(timeout=60)
        st, body = _post_path(srv.port, "/drain")
        assert st == 409 and body["state"] == "dead"
    finally:
        srv.drain_and_join(timeout=60)


def test_metrics_renders_during_drain_and_after_shutdown():
    """The controller scrapes /metrics every tick, including while its
    drain is in flight and after the batcher has exited — the render
    must answer 200 (bounded work, no dead-batcher 500, no deadlock)."""
    slow = _SlowDecode()
    cfg, srv = _server(slots=1, hooks=slow, inf={"decode_block_len": 1})
    try:
        port = srv.port
        srv.front._on_drained = None
        results = {}

        def bg():
            results["a"] = serve._post(port, {"prompt": [1, 2, 3],
                                              "max_new_tokens": 30})

        t = threading.Thread(target=bg)
        t.start()
        slow.wait()
        srv.front.begin_drain()
        st, text = _get_text(port, "/metrics")  # mid-drain
        assert st == 200 and "picotron_queue_depth" in text
        t.join(60)
        srv.front.join(timeout=60)
        st, text = _get_text(port, "/metrics")  # batcher loop exited
        assert st == 200 and "picotron_active_slots" in text
        assert results["a"][0] == 200
    finally:
        srv.drain_and_join(timeout=60)


def test_kv_prefixes_enumerates_hot_paths_paged_only():
    """GET /kv/prefixes: the drain-time cache handoff's enumeration
    surface — hottest radix prefixes as root-path token runs (full-page
    chunks plus a possibly-partial tail leaf), 400 on a bad limit, and
    AdmissionError (not a crash) off the contiguous layout."""
    cfg, srv = _server(slots=2, inf={"kv_layout": "paged",
                                     "kv_page_len": 8,
                                     "decode_block_len": 1})
    try:
        port = srv.port
        shared = list(range(1, 17))  # two whole pages
        for tail in ([21, 22], [31, 32]):
            st, _ = serve._post(port, {"prompt": shared + tail,
                                       "max_new_tokens": 4})
            assert st == 200
        st, body = serve._get(port, "/kv/prefixes?limit=4")
        assert st == 200 and body["prefixes"]
        ids = body["prefixes"][0]["ids"]
        assert len(ids) >= len(shared) and ids[: len(shared)] == shared
        assert body["prefixes"][0]["tenant"] is None
        st, body = serve._get(port, "/kv/prefixes?limit=0")
        assert st == 400
    finally:
        srv.drain_and_join(timeout=60)

    cfg, srv = _server()  # contiguous layout: the kv-transport 503,
    try:                  # same contract as /kv/export — never a crash
        st, body = serve._get(srv.port, "/kv/prefixes")
        assert st == 503 and "paged" in body["error"]
    finally:
        srv.drain_and_join(timeout=60)
