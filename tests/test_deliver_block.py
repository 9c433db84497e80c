"""Delivery a slot and round at a time (ISSUE 41).

A round's tokens reach the accounting, the streaming hook and the socket as
ONE hand-off a live slot (``batcher._tokens_done`` / ``on_tokens`` /
``_Waiter.put_tokens`` / one ``wfile.write``), not one a token. The
contracts:

- what a request receives does not depend on the unit: token sequence,
  finish reason and TTFT presence equal a ``decode_block_len: 1`` run's for
  an EOS in the middle of a block, a budget that ends in the middle of a
  block and the ``max_seq_len`` cut, serial and pipelined;
- rows behind the stop are dropped, whatever the round handed over;
- one ``on_tokens`` call a live slot and round (a list of one at
  admission), ``picotron_stream_events_total`` equal to the calls, the
  token counters (global and by tenant) equal to the tokens streamed;
- a verify round's uneven counts go through the same hand-off;
- over HTTP one NDJSON row a token, byte for byte ``json.dumps`` of the
  row, in order, the ``done`` row last, and one ``wfile.write`` an event.
"""

import json
import math

import pytest

import jax

from conftest import make_config
from picotron_tpu.inference import ContinuousBatcher, InferenceEngine, Request
from picotron_tpu.models import llama
from picotron_tpu.obs import MetricsRegistry, Obs, SpanTracer
from picotron_tpu.obs.metrics import parse_prometheus

MAX_LEN = 48
BLOCK = 4

_TINY = dict(
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
    hidden_size=32, intermediate_size=64, vocab_size=128,
    max_position_embeddings=MAX_LEN, rope_theta=10000.0, dtype="float32",
    attention_impl="sdpa")

_KEPT: dict = {}


def _engine(block=BLOCK, slots=3, **kw):
    """(engine, params), kept by what it was built from (a build compiles
    its programs anew); every call gets a registry of its own."""
    key = json.dumps([block, slots, kw], sort_keys=True)
    if key not in _KEPT:
        cfg = make_config(dict(_TINY), seq=MAX_LEN)
        spec_len = kw.pop("spec_len", None)
        for k, v in kw.items():
            setattr(cfg.inference, k, v)
        engine = InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN,
                                 decode_block_len=block, spec_len=spec_len)
        params = engine.shard_params(jax.jit(
            lambda k: llama.init_params(k, cfg.model))(
                jax.random.PRNGKey(0)))
        _KEPT[key] = (engine, params)
    engine, params = _KEPT[key]
    engine.obs = Obs(enabled=True, registry=MetricsRegistry(),
                     tracer=SpanTracer(ring=4096))
    return engine, params


def _run(engine, params, reqs):
    """Run ``reqs`` to the end: (results, [(uid, tokens)] hook calls,
    batcher)."""
    calls = []
    b = ContinuousBatcher(engine, params,
                          on_tokens=lambda uid, toks: calls.append(
                              (uid, list(toks))))
    res = b.run([Request(**r) for r in reqs])
    return res, calls, b


def _greedy(prompt, n):
    """The greedy stream of ``prompt``, a token a round."""
    engine, params = _engine(block=1)
    res, _, _ = _run(engine, params, [dict(
        uid="g", prompt=prompt, max_new_tokens=n)])
    return res["g"].tokens


def _eos_mid_block():
    """A prompt and an EOS id that first shows in the MIDDLE of a decode
    block (not its last row, not the admission's token)."""
    for seed in range(1, 40):
        prompt = [(seed * 7 + 3 * j) % 120 + 1 for j in range(5)]
        stream = _greedy(prompt, 1 + 3 * BLOCK)
        for k in range(1, len(stream)):
            if (k - 1) % BLOCK < BLOCK - 1 and stream[k] not in stream[:k]:
                return prompt, stream[k], k
    raise AssertionError("no prompt draws a fresh token mid-block")


def _stop_cases():
    prompt, eos, k = _eos_mid_block()
    return {
        # the EOS lands on row (k - 1) % BLOCK of a block: rows behind it
        # are not the request's
        "eos_mid_block": (dict(prompt=prompt, max_new_tokens=30, eos_id=eos),
                          "eos", k + 1),
        # 1 at admission + one whole block + 2 rows of the next
        "budget_mid_block": (dict(prompt=[5, 9, 2], max_new_tokens=BLOCK + 3),
                             "length", BLOCK + 3),
        # the window ends 2 rows into the second block
        "window_cut": (dict(prompt=list(range(1, MAX_LEN - BLOCK - 2)),
                            max_new_tokens=40), "length", BLOCK + 3),
    }


@pytest.mark.parametrize("overlap", [False, True], ids=["serial", "overlap"])
@pytest.mark.parametrize("case", ["eos_mid_block", "budget_mid_block",
                                  "window_cut"])
def test_blocked_delivery_equals_per_token(case, overlap):
    """Token sequence, finish reason and TTFT presence of every request are
    those of a token-a-round run, and the hook saw exactly those tokens,
    each once, in order."""
    spec, reason, n = _stop_cases()[case]
    # a companion keeps a second slot live, so the stopped slot's rows sit
    # beside a slot that goes on
    reqs = [dict(uid="a", **spec),
            dict(uid="b", prompt=[7, 7, 3], max_new_tokens=2 * BLOCK + 2)]
    want, _, _ = _run(*_engine(block=1), reqs)
    inf = {"overlap": True, "key_schedule": "slot"} if overlap else {}
    got, calls, b = _run(*_engine(**inf), reqs)
    assert want["a"].finish_reason == reason and len(want["a"].tokens) == n
    for uid in ("a", "b"):
        assert got[uid].tokens == want[uid].tokens, uid
        assert got[uid].finish_reason == want[uid].finish_reason, uid
        assert (got[uid].ttft_s is None) == (want[uid].ttft_s is None), uid
        streamed = [t for u, toks in calls if u == uid for t in toks]
        assert streamed == got[uid].tokens, uid
    assert b.generated_tokens == sum(len(r.tokens) for r in got.values())


@pytest.mark.parametrize("stop,reason,kept", [
    ("eos", "eos", 3), ("budget", "length", 2), ("window", "length", 2),
    ("none", None, 4)])
def test_rows_behind_the_stop_are_dropped(stop, reason, kept):
    """``_tokens_done`` finds the stop once in what the round handed over:
    the EOS where the round drew it, else the budget / window cut; what
    lies behind it is neither recorded nor streamed (a pipelined round's
    budget may overshoot: the host's rules cut)."""
    engine, params = _engine()
    calls = []
    b = ContinuousBatcher(engine, params,
                          on_tokens=lambda u, t: calls.append(list(t)))
    prompt = (list(range(1, MAX_LEN - 2)) if stop == "window" else [4, 5, 6])
    b.submit(Request("r", prompt, max_new_tokens=3 if stop == "budget"
                     else 20, eos_id=99 if stop == "eos" else None))
    b._admit()
    (i,) = [j for j, s in enumerate(b._slots) if s is not None]
    first = list(b._slots[i].generated)
    assert calls == [first] and len(first) == 1
    handed = [11, 12, 99, 13]
    b._tokens_done(i, list(handed))
    assert calls[1] == handed[:kept]
    assert b.generated_tokens == 1 + kept
    assert b._stream_events_total.value == 2
    if reason is None:
        assert b._slots[i].generated == first + handed
        assert b._last_tok[i] == handed[-1]
    else:
        res = b.take_results()["r"]
        assert res.finish_reason == reason
        assert res.tokens == first + handed[:kept]
        assert b._slots[i] is None


@pytest.mark.parametrize("lane", [False, True], ids=["serial", "mixed"])
def test_one_event_a_slot_and_round(lane):
    """One hook call a live slot and round: a list of one where the first
    token is drawn (admission, or the fused lane's final chunk), then whole
    blocks, then the rest; the events counter equals the calls and the
    token counters, global and by tenant, what was streamed."""
    inf = ({"mixed_dispatch": True, "prefill_chunk": 8,
            "key_schedule": "slot"} if lane else {})
    engine, params = _engine(**inf)
    budgets = {"t0": 1 + 2 * BLOCK, "t1": 1 + BLOCK + 1, "t2": 3,
               "t3": 1 + 3 * BLOCK}
    reqs = [dict(uid=uid, prompt=[3 + j, 5, 7] * (4 if lane else 1),
                 max_new_tokens=n, tenant="acme" if j % 2 else "")
            for j, (uid, n) in enumerate(budgets.items())]
    res, calls, b = _run(engine, params, reqs)
    for uid, n in budgets.items():
        mine = [toks for u, toks in calls if u == uid]
        assert [len(t) for t in mine] == (
            [1] + [BLOCK] * ((n - 1) // BLOCK)
            + ([(n - 1) % BLOCK] if (n - 1) % BLOCK else [])), uid
        assert len(mine) == 1 + math.ceil((n - 1) / BLOCK)
        assert [t for toks in mine for t in toks] == res[uid].tokens
    prom = parse_prometheus(engine.obs.registry.prometheus())
    total = sum(budgets.values())
    assert prom["picotron_stream_events_total"] == len(calls)
    assert prom["picotron_generated_tokens_total"] == total
    assert total / len(calls) > 2  # more than a token an event
    by_tenant = {"acme": budgets["t1"] + budgets["t3"],
                 "base": budgets["t0"] + budgets["t2"]}
    for tenant, n in by_tenant.items():
        assert prom[f'picotron_tenant_tokens_total{{tenant="{tenant}"}}'] \
            == n
        assert b.stats()["tenants"][tenant]["tokens"] == n


def test_verify_rounds_uneven_counts_one_event_each():
    """A speculative engine's rounds emit 1 to spec_len + 1 tokens a slot:
    each is one hand-off, and the streams are the spec-off run's."""
    reqs = [dict(uid="s0", prompt=[2, 9, 2, 9, 2, 9], max_new_tokens=14),
            dict(uid="s1", prompt=[4, 4, 4, 4], max_new_tokens=9)]
    want, _, _ = _run(*_engine(block=1), reqs)
    engine, params = _engine(block=1, spec_len=3)
    got, calls, b = _run(engine, params, reqs)
    for uid in ("s0", "s1"):
        assert got[uid].tokens == want[uid].tokens
        assert [t for u, toks in calls if u == uid for t in toks] \
            == got[uid].tokens
    assert all(1 <= len(toks) <= 4 for _, toks in calls)
    assert b._stream_events_total.value == len(calls)
    # every verify dispatch handed each of its live slots one event
    assert len(calls) <= 2 + 2 * b.decode_dispatches


class _Tap:
    """A handler's ``wfile`` that notes every write."""

    def __init__(self, inner, writes):
        self._inner, self._writes = inner, writes

    def write(self, data):
        self._writes.append(bytes(data))
        return self._inner.write(data)

    def __getattr__(self, name):
        return getattr(self._inner, name)


@pytest.fixture
def tapped_server(monkeypatch):
    from picotron_tpu.tools import serve

    writes, events = [], []
    setup = serve._Handler.setup

    def tapped(self):
        setup(self)
        self.wfile = _Tap(self.wfile, writes)

    monkeypatch.setattr(serve._Handler, "setup", tapped)
    engine, params = _engine()
    srv = serve.Server(engine, params, port=0, log=lambda *a, **k: None)
    hook = srv.front._batcher.on_tokens

    def noting(uid, toks):
        events.append((uid, list(toks)))
        hook(uid, toks)

    srv.front._batcher.on_tokens = noting
    srv.start()
    try:
        yield serve, srv, writes, events
    finally:
        srv.drain_and_join(timeout=60)


def test_stream_one_write_an_event_rows_as_before(tapped_server):
    """The wire is what it was: one NDJSON row a token, each byte for byte
    ``json.dumps`` of its row, in order, the ``done`` row last; and the
    rows of one event leave in ONE write."""
    serve, srv, writes, events = tapped_server
    n = 1 + 2 * BLOCK + 2
    spec = {"prompt": [1, 2, 3], "max_new_tokens": n, "uid": "w1",
            "request_id": "rid-1", "stream": True}
    st, rows = serve._post(srv.port, spec, stream=True)
    assert st == 200 and len(rows) == n + 1
    toks = [r["token"] for r in rows[:-1]]
    assert all(r["event"] == "token" for r in rows[:-1])
    done = rows[-1]
    assert done["event"] == "done" and done["tokens"] == toks
    assert done["finish_reason"] == "length"
    body = [w for w in writes if w.startswith(b'{"event"')]
    mine = [t for u, t in events if u == "w1"]
    assert [len(t) for t in mine] == [1, BLOCK, BLOCK, 2]
    # one write an event, then the done row's
    assert len(body) == len(mine) + 1
    for w, ev in zip(body, mine):
        assert w == "".join(
            json.dumps({"event": "token", "uid": "w1",
                        "request_id": "rid-1", "token": t}) + "\n"
            for t in ev).encode()
    assert json.loads(body[-1]) == done and body[-1].endswith(b"\n")
    assert body[-1].count(b"\n") == 1


def test_metrics_show_tokens_an_event(tapped_server):
    """``/metrics`` carries the events beside the tokens; a request that
    does not stream is counted alike and skips the token events."""
    serve, srv, writes, events = tapped_server
    n = 1 + 3 * BLOCK
    st, body = serve._post(srv.port, {"prompt": [9, 8, 7], "uid": "m1",
                                      "max_new_tokens": n})
    assert st == 200 and len(body["tokens"]) == n
    st, rows = serve._post(srv.port, {"prompt": [9, 8, 7], "uid": "m2",
                                      "max_new_tokens": n, "stream": True},
                           stream=True)
    assert [r["token"] for r in rows[:-1]] == body["tokens"]  # greedy
    st, text = serve._get_text(srv.port, "/metrics")
    prom = parse_prometheus(text)
    assert prom["picotron_stream_events_total"] == len(events) == 2 * 4
    assert prom["picotron_generated_tokens_total"] == 2 * n
    assert not [w for w in writes if b'"uid": "m1"' in w
                and b'"event"' in w]


def test_a_burst_of_connections_is_held_not_dropped():
    """A closed loop's clients all connect at once (128 in the Nemotron
    cell). The listening socket holds them until the accept loop takes
    them: past a backlog of 5, socketserver's default, the kernel drops the
    SYN and the client's next one comes 1, 3, 7, ... 63 s later (ISSUE 56:
    one of 128 streams began 63 s behind the rest). Here nobody accepts at
    all, and 96 connections still complete at once."""
    import socket

    from picotron_tpu.tools import serve

    engine, params = _engine()
    srv = serve.Server(engine, params, port=0, log=lambda *a, **k: None)
    socks = []
    try:
        assert srv.httpd.request_queue_size >= 1024
        for _ in range(96):
            socks.append(socket.create_connection(("127.0.0.1", srv.port),
                                                  timeout=0.5))
    finally:
        for s in socks:
            s.close()
        srv.httpd.server_close()
