"""A round crosses the host-device boundary once each way (ISSUE 38;
docs/INFERENCE.md "The round program's signature").

- the packed round is the parent's round, bit for bit: every round's
  ``tokens``, ``counts``, ``accepted`` and the cache it leaves equal what
  the SAME bodies give when their rows come up as separate operands built
  the parent's way (six ``jnp.asarray`` copies, float32 rows as float32)
  and their outputs come down as separate arrays fetched after the wait;
  over the four blocks, ``decode_block`` and ``verify``, both key
  schedules, the overlap pipeline's device ``tokens`` row, the fused
  prefill lane, ``dp_size`` 2 and a paged cache, with ``temperature`` 0.7
  and ``top_p`` 0.9 (the bit-cast has to be exact);
- ``picotron_round_copies_total``: one copy up and one down a serial round
  of every block, one up under ``overlap`` (the device row is no copy), a
  re-dispatch counting again (``tests/test_round_parts.py``), and the parts
  still observed once a dispatch.
"""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from picotron_tpu.inference import ContinuousBatcher, Request
from picotron_tpu.inference.engine import RoundResult
from picotron_tpu.obs import MetricsRegistry, Obs, SpanTracer
from picotron_tpu.obs.metrics import parse_prometheus
from picotron_tpu.utils import shard_map
from test_obs import _engine as _llama_engine
from test_round_parts import _part_reads


def _llama(**inf):
    return _llama_engine(slots=inf.pop("slots", 2), decode_block_len=2,
                         **inf)[1:]


def _block(module, monkeypatch):
    import importlib

    mod = importlib.import_module(module)
    if module == "test_minicpm_sala":
        # its prefill runs in scan chunks of 8 at toy size, as its own
        # tests' (their autouse fixture); put back with the test
        monkeypatch.setattr(mod.sala, "SCAN_CHUNK", 8)
    return mod.make_engine(decode_block_len=2)[1:]


# name -> (how the engine is made, the kind of round its batcher runs); an
# engine's bring-up is most of a case's time, so options that compose share
# one: between them the eight hold both kinds of round under both key
# schedules, a device tokens row, the fused lane, dp 2 and a paged cache
CASES = {
    "llama": (lambda mp: _llama(), "decode_block"),
    "deepseek_v32": (lambda mp: _block("test_deepseek_v32", mp),
                     "decode_block"),
    "granitemoehybrid": (lambda mp: _block("test_granite_hybrid", mp),
                         "decode_block"),
    "minicpm_sala": (lambda mp: _block("test_minicpm_sala", mp),
                     "decode_block"),
    # the round schedule's verify, the learned drafter's hidden rows
    "llama-verify-hidden": (lambda mp: _llama(spec_len=3,
                                              return_hidden=True), "verify"),
    # the lookahead pipeline hands its tokens row in on the device and
    # runs under the slot schedule
    "llama-overlap": (lambda mp: _llama(overlap=True), "decode_block"),
    # the slot schedule's verify with the fused prefill lane behind it
    "llama-mixed-verify": (lambda mp: _llama(
        mixed_dispatch=True, prefill_chunk=8, key_schedule="slot",
        spec_len=3), "verify"),
    # the pack split on its slot axis; the page tables' advance reads the
    # packed counts
    "llama-dp2-paged": (lambda mp: _llama(slots=4, dp_size=2,
                                          kv_layout="paged", kv_page_len=8),
                        "decode_block"),
}


@contextlib.contextmanager
def _the_parents_way(eng):
    """``eng``'s rounds as the parent made them: six (seven in a verify)
    separate host-to-device copies, a program that takes them as operands
    of their own and hands back ``tokens``, ``counts`` and ``accepted`` as
    arrays of their own, each fetched by its own ``np.asarray`` after the
    program ended. The bodies are the engine's; nothing of them unpacks."""
    fields = eng._round_fields
    programs = {}

    def parent_fields(kind):
        own = ("tokens", "counts") + (("accepted",) if kind == "verify"
                                      else ())
        return tuple(n for f in fields(kind)
                     for n in (own if f == "packed" else (f,)))

    def operands(rows, keys, eos_id, budget, temperature, top_k, top_p,
                 lanes):
        return (*(jnp.asarray(r) for r in rows), keys,
                jnp.asarray(np.asarray(eos_id, np.int32)),
                jnp.asarray(np.asarray(budget, np.int32)),
                jnp.asarray(np.asarray(temperature, np.float32)),
                jnp.asarray(np.asarray(top_k, np.int32)),
                jnp.asarray(np.asarray(top_p, np.float32)),
                *(eng._lane_args(lanes) if eng.mixed else ()))

    def build(kind, poison):
        n = 2 if kind == "verify" else 1
        impl = eng._verify_impl if kind == "verify" \
            else eng._decode_block_impl

        def body(params, cache, *ops):
            rows, (key, *rest) = ops[:n], ops[n:]
            five, lane = rest[:5], rest[5:]
            # trace time only: the body reads its rows by their names
            # and returns the parent's fields
            eng._unpack_rows = lambda *_: ((*rows, *five), (key, *lane))
            eng._round_fields = parent_fields
            try:
                return impl(params, cache, None, poison=poison)
            finally:
                del eng._unpack_rows, eng._round_fields

        dpP = P("dp") if eng.dp_size > 1 else P()
        keys = dpP if eng.key_schedule == "slot" else P()
        lane = (dpP,) * (4 + 4 * eng.sample_on_device
                         + (eng.adapters is not None) if eng.mixed else 0)
        return jax.jit(shard_map(
            body, eng.topo.mesh,
            in_specs=(eng._decode_dispatch_pspecs, eng._cspecs)
            + (dpP,) * n + (keys,) + (dpP,) * 5 + lane,
            out_specs=tuple(eng._cspecs if f == "cache"
                            else P() if f == "stats" else dpP
                            for f in parent_fields(kind))),
            donate_argnums=(1,))

    def program(kind, poison=False, dev_tokens=False):
        if (kind, poison) not in programs:
            programs[kind, poison] = build(kind, poison)

        def run(params, cache, *ops):
            out = dict(zip(parent_fields(kind),
                           programs[kind, poison](params, cache, *ops)))
            jax.block_until_ready(out["tokens"])
            host = [np.asarray(out.pop("tokens")),
                    np.asarray(out.pop("counts"))[:, None]]
            if kind == "verify":
                host.append(np.asarray(out.pop("accepted"))[:, None])
            out["packed"] = jnp.asarray(np.concatenate(host, axis=1))
            return tuple(out[f] for f in fields(kind))

        return run

    eng._round_operands, eng._program = operands, program
    try:
        yield
    finally:
        del eng._round_operands, eng._program


def _requests():
    """Two slots' worth and one more, so a slot is admitted into twice;
    float rows that are no round numbers, and a greedy row beside them."""
    return [Request("a", [3, 5, 7, 9, 11], max_new_tokens=9,
                    temperature=0.7, top_k=0, top_p=0.9),
            Request("b", [4, 6, 8], max_new_tokens=7,
                    temperature=0.7, top_k=5, top_p=0.9),
            Request("c", list(range(2, 13)), max_new_tokens=6),
            Request("d", [9, 8, 7, 6], max_new_tokens=8,
                    temperature=1.3, top_k=0, top_p=0.35)]


def _run(engine, params):
    """One batcher's life on a registry of its own: (every round's kind and
    host outputs, the streams, the cache it left, the copies counted after
    the first round and after the last, the dispatches)."""
    rounds = []
    inner = engine._round

    def recorded(kind, *a, **kw):
        res = inner(kind, *a, **kw)
        rounds.append((kind, res))
        return res

    engine._round = recorded
    registry = _fresh_obs(engine)
    try:
        b = ContinuousBatcher(engine, params, seed=38)
        for r in _requests():
            b.submit(r)
        first = None
        while b.busy:
            b.step()
            if first is None and b.decode_dispatches:
                first = _copies(registry)
        streams = {u: (r.tokens, r.finish_reason)
                   for u, r in b.take_results().items()}
    finally:
        del engine._round
    return ([(k, *r.host(), r.next_tok, r.hidden) for k, r in rounds],
            streams, b._cache, (first, _copies(registry)),
            b.decode_dispatches)


def _fresh_obs(engine):
    engine.obs = Obs(enabled=True, registry=MetricsRegistry(),
                     tracer=SpanTracer(ring=4096))
    return engine.obs.registry


def _copies(registry):
    prom = parse_prometheus(registry.prometheus())
    return {d: prom.get(f'picotron_round_copies_total{{direction="{d}"}}', 0)
            for d in ("h2d", "d2h")}


def _same(a, b, what):
    if a is None or b is None:
        assert a is None and b is None, what
        return
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape, what
    assert a.tobytes() == b.tobytes(), what


@pytest.mark.parametrize("case", list(CASES))
def test_a_packed_round_is_the_parents_round_bit_for_bit(case, monkeypatch):
    make, kind = CASES[case]
    engine, params = make(monkeypatch)
    got, streams, cache, (first, last), n = _run(engine, params)
    parts = _part_reads(engine.obs.registry)
    with _the_parents_way(engine):
        want, ref_streams, ref_cache, _, _ = _run(engine, params)
    assert streams == ref_streams and set(streams) == set("abcd")
    assert len(got) == len(want) >= 4 and {r[0] for r in got} == {kind}
    for i, (g, w) in enumerate(zip(got, want)):
        for name, x, y in zip(("tokens", "counts", "accepted", "next_tok",
                               "hidden"), g[1:], w[1:]):
            _same(x, y, (case, i, name))
    # the rounds produced tokens: equal outputs are not equal zeros
    assert any(np.asarray(r[2]).sum() for r in got)
    assert jax.tree.structure(cache) == jax.tree.structure(ref_cache)
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(cache),
                            jax.tree.leaves(ref_cache)):
        _same(x, y, (case, jax.tree_util.keystr(path)))
    # a host tokens row rides in the pack, a device row beside it
    dev = "overlap" in case
    assert {k[2] for k in engine._programs if k[0] == kind} == {dev}
    # ONE copy up and ONE down a round (``picotron_round_copies_total``):
    # the pipeline's device row is no copy (its first round is still in
    # flight when the step returns); a mixed engine's lane operands count
    # beside the pack; the parts are observed once a dispatch, as they were
    assert n == len(got)
    up = 1 + (len(engine._lane_args(None)) if engine.mixed else 0)
    assert first == {"h2d": up, "d2h": 0 if dev else 1}
    assert last == {"h2d": n * up, "d2h": n}
    assert {p: v["count"] for p, v in parts.items()} == dict.fromkeys(
        ("issue/operands", "issue/enqueue", "sync/wait", "sync/fetch"), n)


def test_the_float_rows_travel_as_their_bits():
    """What ``_round_operands`` packs, ``_unpack_rows`` hands back: every
    row under its name, float32 rows bit for bit (a NaN's payload and a
    negative zero among them), a verify's tokens [slots, S] and ``valid``,
    a device tokens row left beside the pack."""
    engine, _ = _llama(spec_len=3)
    n, S = engine.slots, engine.spec_len + 1
    odd = np.array([0.7, -0.0], np.float32)
    odd_p = np.frombuffer(np.array([0x7FC00123, 0x3F666666], np.uint32),
                          np.float32)  # a NaN with a payload, 0.9
    rows = dict(eos_id=np.array([-1, 7], np.int32),
                budget=np.array([2, 0], np.int32),
                temperature=odd, top_k=np.array([0, 5], np.int32),
                top_p=odd_p)
    keys = jnp.zeros((2, 2), jnp.uint32)
    for kind, tokens, valid in (
            ("decode_block", np.array([11, 12], np.int32), ()),
            ("verify", np.arange(n * S, dtype=np.int32).reshape(n, S),
             (np.array([4, 2], np.int32),)),
            ("decode_block", jnp.array([11, 12], jnp.int32), ())):
        dev = isinstance(tokens, jax.Array)
        pack, *rest = engine._round_operands((tokens, *valid), keys,
                                             lanes=None, **rows)
        # a host array still: the program's call takes it up
        assert isinstance(pack, np.ndarray) and pack.dtype == np.int32 \
            and pack.shape == (
                5 + len(valid) + (0 if dev else tokens.size // n), n)
        assert (rest[0] is tokens) == dev and rest[-1] is keys
        got, left = jax.jit(
            lambda p, *r: engine._unpack_rows(kind, p, r, dev))(pack, *rest)
        assert len(left) == 1
        names = ("tokens", *("valid",) * len(valid), "eos_id", "budget",
                 "temperature", "top_k", "top_p")
        want = dict(rows, tokens=tokens, **({"valid": valid[0]} if valid
                                            else {}))
        assert len(got) == len(names)
        for name, g in zip(names, got):
            _same(g, want[name], (kind, name))


def test_round_result_reads_one_copy():
    """``RoundResult``'s host fields are views of the one packed array."""
    packed = jnp.arange(12, dtype=jnp.int32).reshape(2, 6)
    block = RoundResult(cache={}, packed=packed)
    assert block.tokens.tolist() == [[0, 1, 2, 3, 4], [6, 7, 8, 9, 10]]
    assert block.counts.tolist() == [5, 11] and block.accepted is None
    ver = RoundResult(cache={}, packed=packed, verify=True)
    assert ver.tokens.tolist() == [[0, 1, 2, 3], [6, 7, 8, 9]]
    assert ver.counts.tolist() == [4, 10]
    assert ver.accepted.tolist() == [5, 11]
