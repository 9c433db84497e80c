"""Inference subsystem tests (picotron_tpu/inference/).

Covers the ISSUE-1 acceptance surface: (a) prefill + KV-cache decode_step
greedy generation exactly matches the full-sequence ``forward_logits``
argmax, on tp=1 AND a tp=2 dryrun mesh; (b) the samplers are
distribution-correct under fixed keys; (c) the continuous batcher recycles
slots across mixed-length requests without cross-request interference;
(d) a training checkpoint (including an uneven-pp padded layer stack)
round-trips through ``CheckpointManager.load_params`` into the engine.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from conftest import HEADS, KV_PACK, make_config, with_heads
from picotron_tpu import checkpoint as ckpt
from picotron_tpu import train_step as ts
from picotron_tpu.inference import (
    ContinuousBatcher,
    InferenceEngine,
    Request,
    kv_cache,
    sampling,
)
from picotron_tpu.models import llama
from picotron_tpu.topology import named_shardings, topology_from_config
from picotron_tpu.utils import shard_map as shard_map_compat

MAX_LEN = 96


def _engine(tiny_model_kwargs, tp=1, slots=2, **kw):
    cfg = make_config(tiny_model_kwargs, tp=tp, seq=MAX_LEN)
    return cfg, InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN, **kw)


def _params(cfg, engine, seed=0):
    p = jax.jit(lambda k: llama.init_params(k, cfg.model))(
        jax.random.PRNGKey(seed))
    return engine.shard_params(p)


def _oracle_logits(cfg, engine, params, seq):
    """Full-sequence logits [S, V] from forward_logits — the training-side
    oracle the KV-cache path must reproduce."""
    fwd = jax.jit(shard_map_compat(
        lambda p, t: llama.forward_logits(p, t, cfg),
        engine.topo.mesh,
        in_specs=(llama.param_pspecs(cfg.model), P()),
        out_specs=P()))
    toks = jnp.asarray(np.asarray(seq, np.int32)[None, :])
    return np.asarray(fwd(params, toks))[0]


# --------------------------------------------------------------------------- #
# (a) prefill + decode == full forward
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("heads", list(HEADS))
@pytest.mark.parametrize("tp", [1, 2])
def test_greedy_decode_matches_full_forward(tiny_model_kwargs, tp, heads):
    """32 greedy tokens from prefill + decode_step must equal the
    full-sequence argmax chain, exactly, on tp=1 and a tp=2 dryrun mesh
    (the tiny model is GQA: 8 q-heads over 4 kv-heads), whatever the heads
    a row of the cache holds: one, two of 64, four of 32, and the fallback
    to one where tp leaves a shard fewer kv heads than fill a row."""
    cfg, engine = _engine(with_heads(tiny_model_kwargs, heads), tp=tp)
    assert engine.kv_pack == KV_PACK[heads][tp - 1]
    assert engine.init_cache()["k"].shape[-1] \
        == engine.kv_pack * cfg.model.head_dim
    params = _params(cfg, engine)
    prompt = list(range(1, 9))
    n_new = 32
    res = ContinuousBatcher(engine, params).run(
        [Request("r", prompt, max_new_tokens=n_new)])["r"]
    assert len(res.tokens) == n_new
    # one oracle pass over the final sequence verifies every step: greedy
    # means seq[i+1] must be argmax of the full-forward logits at i
    seq = prompt + res.tokens
    pred = np.argmax(_oracle_logits(cfg, engine, params, seq), axis=-1)
    for i in range(len(prompt) - 1, len(seq) - 1):
        assert pred[i] == seq[i + 1], (i, pred[i], seq[i + 1])


@pytest.mark.parametrize("heads", ["d8", "d64", "d32", "d128"])
def test_prefill_logits_match_full_forward(tiny_model_kwargs, heads):
    """The prefill's last-token logits are the full forward's, to fp32
    tolerance, for several prompt lengths (bucket padding must be inert),
    and so are a decode step's through the cache the prefill's blocks were
    parked in, packed or a head a row."""
    cfg, engine = _engine(with_heads(tiny_model_kwargs, heads))
    params = _params(cfg, engine)
    for n in (1, 5, 16):
        prompt = [(7 * i + 3) % cfg.model.vocab_size for i in range(n)]
        kv, last = engine.prefill(params, prompt)
        want = _oracle_logits(cfg, engine, params, prompt)[n - 1]
        np.testing.assert_allclose(np.asarray(last)[0], want,
                                   rtol=1e-5, atol=1e-5)
    assert kv["k"].shape[3:] == engine.init_cache()["k"].shape[3:]
    cache = engine.insert(engine.init_cache(), kv, 1, n)
    feed = np.zeros(engine.slots, np.int32)
    feed[1] = 11
    zeros = np.zeros(engine.slots, np.float32)
    _, _, logits = engine.decode_step(
        params, cache, feed, jax.random.PRNGKey(0), zeros,
        np.zeros(engine.slots, np.int32), zeros + 1)
    want = _oracle_logits(cfg, engine, params, prompt + [11])[n]
    np.testing.assert_allclose(np.asarray(logits)[1], want,
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# (b) samplers
# --------------------------------------------------------------------------- #


def test_sample_zero_temperature_is_greedy():
    logits = jnp.asarray(
        np.random.default_rng(0).normal(size=(3, 17)).astype(np.float32))
    want = np.argmax(np.asarray(logits), axis=-1)
    for seed in range(4):
        got = sampling.sample(
            logits, jax.random.PRNGKey(seed), jnp.zeros(3),
            jnp.zeros(3, jnp.int32), jnp.ones(3))
        np.testing.assert_array_equal(np.asarray(got), want)


def test_top_k_filter_keeps_k_highest():
    logits = jnp.asarray(
        np.random.default_rng(1).normal(size=(2, 32)).astype(np.float32))
    out = np.asarray(sampling.apply_top_k(logits, jnp.asarray([3, 0])))
    kept0 = np.flatnonzero(out[0] > -1e29)
    assert set(kept0) == set(np.argsort(np.asarray(logits)[0])[-3:])
    np.testing.assert_array_equal(out[1], np.asarray(logits)[1])  # k<=0: off


def test_top_p_filter_keeps_minimal_nucleus():
    # probs 0.5, 0.3, 0.1, 0.1: p=0.7 keeps {0, 1} (exclusive prefix mass
    # 0.0 and 0.5 < 0.7; token 2's 0.8 is out); p>=1 keeps everything
    probs = np.array([[0.5, 0.3, 0.1, 0.1]], np.float32)
    logits = jnp.asarray(np.log(probs))
    out = np.asarray(sampling.apply_top_p(logits, jnp.asarray([0.7])))
    assert set(np.flatnonzero(out[0] > -1e29)) == {0, 1}
    out_off = np.asarray(sampling.apply_top_p(logits, jnp.asarray([1.0])))
    np.testing.assert_array_equal(out_off, np.asarray(logits))


def test_fused_filter_matches_sequential_application():
    """filter_top_k_top_p (one sort, what ``sample`` runs) must keep
    exactly the token set of the sequential apply_top_k -> apply_top_p
    application — randomized logits WITH exact ties (quantized values make
    threshold collisions common), across k/p combinations including the
    disabled sentinels."""
    rng = np.random.default_rng(7)
    V = 24
    # quantize to force exact ties at top-k thresholds and nucleus cutoffs
    logits = np.round(rng.normal(size=(64, V)) * 4) / 4
    logits = jnp.asarray(logits.astype(np.float32))
    for k in (0, 1, 3, V, V + 5):
        for p in (0.05, 0.3, 0.7, 0.95, 1.0):
            ks = jnp.full(logits.shape[0], k, jnp.int32)
            ps = jnp.full(logits.shape[0], p, jnp.float32)
            fused = np.asarray(sampling.filter_top_k_top_p(logits, ks, ps))
            seq = np.asarray(
                sampling.apply_top_p(sampling.apply_top_k(logits, ks), ps))
            np.testing.assert_array_equal(fused > -1e29, seq > -1e29,
                                          err_msg=f"k={k} p={p}")
            # surviving logits pass through unchanged
            np.testing.assert_array_equal(
                np.where(fused > -1e29, fused, 0),
                np.where(seq > -1e29, np.asarray(logits), 0))


def test_top_p_zero_pins_top1():
    """p <= 0 would mask every column (exclusive prefix mass 0 < 0 is
    False); both filters must pin the top-1 token instead of degenerating
    into a constant token-0 emitter."""
    logits = jnp.asarray(
        np.random.default_rng(3).normal(size=(4, 16)).astype(np.float32))
    best = np.argmax(np.asarray(logits), axis=-1)
    for p in (0.0, -1.0):
        ps = jnp.full(4, p, jnp.float32)
        for out in (sampling.apply_top_p(logits, ps),
                    sampling.filter_top_k_top_p(
                        logits, jnp.zeros(4, jnp.int32), ps)):
            kept = np.asarray(out) > -1e29
            np.testing.assert_array_equal(np.sum(kept, axis=-1),
                                          np.ones(4))
            assert all(kept[i, best[i]] for i in range(4))
        # and sampling at any temperature draws exactly the argmax
        got = sampling.sample(logits, jax.random.PRNGKey(0),
                              jnp.ones(4), jnp.zeros(4, jnp.int32), ps)
        np.testing.assert_array_equal(np.asarray(got), best)


def test_sample_distribution_matches_softmax():
    """Temperature-1 sampling frequencies converge to softmax; with top_k
    the support restricts to the k best and renormalizes."""
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(1, 8)).astype(np.float32))
    n = 4000
    keys = jax.random.split(jax.random.PRNGKey(0), n)

    def draw(top_k):
        toks = jax.vmap(lambda k: sampling.sample(
            logits, k, jnp.ones(1), jnp.asarray([top_k]), jnp.ones(1))[0]
        )(keys)
        return np.bincount(np.asarray(toks), minlength=8) / n

    probs = np.asarray(jax.nn.softmax(logits[0]))
    np.testing.assert_allclose(draw(0), probs, atol=0.04)

    top3 = set(np.argsort(probs)[-3:])
    freq = draw(3)
    assert set(np.flatnonzero(freq)) <= top3
    renorm = np.where(np.isin(np.arange(8), list(top3)), probs, 0)
    np.testing.assert_allclose(freq, renorm / renorm.sum(), atol=0.04)


# --------------------------------------------------------------------------- #
# (c) continuous batching / slot recycling
# --------------------------------------------------------------------------- #


def test_batcher_recycles_slots_mixed_lengths(tiny_model_kwargs):
    """5 mixed-length requests through 2 slots: every request finishes with
    its full budget, and a request's tokens are identical to running it
    alone — slot sharing and recycling must not leak across sequences."""
    cfg, engine = _engine(tiny_model_kwargs, slots=2)
    params = _params(cfg, engine)
    reqs = [
        Request(f"r{i}", [(3 * i + j) % 50 + 1 for j in range(3 + 2 * i)],
                max_new_tokens=5 + 3 * i)
        for i in range(5)
    ]
    batched = ContinuousBatcher(engine, params).run(reqs)
    assert set(batched) == {r.uid for r in reqs}
    for r in reqs:
        res = batched[r.uid]
        assert res.finish_reason == "length"
        assert len(res.tokens) == r.max_new_tokens, r.uid
    for r in (reqs[0], reqs[4]):  # shortest and longest
        solo = ContinuousBatcher(engine, params).run(
            [Request("solo", r.prompt, max_new_tokens=r.max_new_tokens)])
        assert solo["solo"].tokens == batched[r.uid].tokens, r.uid


def test_batcher_request_timeout_frees_slot(tiny_model_kwargs):
    """A request past its wall-clock deadline finishes with reason "timeout"
    and releases its slot, so a queued request behind it still completes —
    driven by an injected clock (1s per scheduler tick) for determinism."""

    class Clock:
        t = 0.0

        def __call__(self):
            self.t += 1.0
            return self.t

    cfg, engine = _engine(tiny_model_kwargs, slots=1)
    params = _params(cfg, engine)
    b = ContinuousBatcher(engine, params, clock=Clock())
    res = b.run([
        Request("hog", [1, 2, 3], max_new_tokens=64, timeout_s=3.0),
        Request("queued", [4, 5, 6], max_new_tokens=4),
    ])
    assert res["hog"].finish_reason == "timeout"
    assert 0 < len(res["hog"].tokens) < 64  # partial output is returned
    assert res["queued"].finish_reason == "length"
    assert len(res["queued"].tokens) == 4
    # no deadline => never times out, identical to the pre-deadline behavior
    free = ContinuousBatcher(engine, params, clock=Clock()).run(
        [Request("a", [1, 2, 3], max_new_tokens=8)])["a"]
    assert free.finish_reason == "length" and len(free.tokens) == 8


def test_batcher_eos_terminates_early(tiny_model_kwargs):
    cfg, engine = _engine(tiny_model_kwargs)
    params = _params(cfg, engine)
    prompt = [5, 6, 7, 8]
    free = ContinuousBatcher(engine, params).run(
        [Request("a", prompt, max_new_tokens=10)])["a"]
    eos = free.tokens[2]
    assert eos not in free.tokens[:2], "pick a different seed/prompt"
    res = ContinuousBatcher(engine, params).run(
        [Request("a", prompt, max_new_tokens=10, eos_id=eos)])["a"]
    assert res.finish_reason == "eos"
    assert res.tokens == free.tokens[:3]


# --------------------------------------------------------------------------- #
# (c') the round schedule's keys: one program a round, kept on the device
# --------------------------------------------------------------------------- #


def _eager_round(key, block):
    """The chain as the batcher walked it before ``engine.round_keys``:
    one eager split and one host copy a decode step."""
    subs = []
    for _ in range(block):
        key, sub = jax.random.split(key)
        subs.append(np.asarray(sub))
    return key, np.stack(subs)


@pytest.mark.parametrize("block", [1, 8])
def test_round_keys_equal_eager_chain(tiny_model_kwargs, block):
    """Three rounds of the key program, an admission's eager split between
    them: keys and carried key equal the eager chain bit for bit."""
    _, engine = _engine(tiny_model_kwargs, decode_block_len=block)
    key = jax.device_put(jax.random.PRNGKey(11), engine.key_sharding)
    eager = jax.random.PRNGKey(11)
    for _ in range(3):
        key, keys = engine.round_keys(key)
        eager, want = _eager_round(eager, block)
        assert isinstance(keys, jax.Array) and keys.shape == (block, 2)
        assert np.array_equal(np.asarray(keys), want)
        assert np.array_equal(np.asarray(key), np.asarray(eager))
        key, sub = jax.random.split(key)  # as _admit's _split() does
        eager, esub = jax.random.split(eager)
        assert np.array_equal(np.asarray(sub), np.asarray(esub))


# tokens of the tree before the key program (PR 28, jax 0.9.0 CPU): the
# chain is the same chain, so a sampled stream is the same stream
_SAMPLED_PINS = {
    8: {"a": [66, 139, 106, 255, 186, 222, 113, 105, 30, 187, 224, 78, 9,
              57, 65, 240, 161, 139, 108, 32],
        "b": [19, 96, 122, 111, 67, 199, 113, 236, 10, 225, 128, 179]},
    1: {"a": [66, 139, 106, 186, 222, 53, 191, 110, 190, 97, 134, 233, 97,
              62, 0, 196, 208, 118, 160, 66],
        "b": [237, 236, 37, 175, 222, 196, 208, 145, 228, 50, 143, 231]},
}


@pytest.mark.parametrize("block", [8, 1])
def test_sampled_streams_equal_parent(tiny_model_kwargs, block):
    """Two sampled requests admitted in different rounds draw the tokens
    they drew while the batcher split the round's keys eagerly."""
    cfg, engine = _engine(tiny_model_kwargs, decode_block_len=block)
    assert engine.key_schedule == "round"
    b = ContinuousBatcher(engine, _params(cfg, engine), seed=7)
    b.submit(Request("a", [5, 6, 7, 8], max_new_tokens=20, temperature=0.9,
                     top_k=40))
    b.step()
    b.step()
    b.submit(Request("b", [9, 10, 11], max_new_tokens=12, temperature=1.3,
                     top_k=8, top_p=0.95))
    res = b.run()
    assert {u: r.tokens for u, r in res.items()} == _SAMPLED_PINS[block]


def test_round_hands_decode_block_device_keys(tiny_model_kwargs,
                                              monkeypatch):
    """A round-keyed ``_step_serial`` round: one key-program dispatch, no
    eager ``jax.random.split`` between admission's end and the issue, and
    ``engine.decode_block`` is handed the keys as a device array."""
    cfg, engine = _engine(tiny_model_kwargs)
    assert engine.key_schedule == "round"
    b = ContinuousBatcher(engine, _params(cfg, engine))
    b.submit(Request("a", [1, 2, 3], max_new_tokens=40, temperature=0.8))
    b.step()  # admission and a first round: the key program is traced
    seen = {"splits": 0, "programs": 0, "issues": []}
    real_split, real_admit = jax.random.split, b._admit
    real_keys, real_block = engine.round_keys, engine.decode_block

    def split(*a, **kw):
        seen["splits"] += 1
        return real_split(*a, **kw)

    def admit():
        real_admit()
        seen["splits"] = 0  # admission's own split is not the round's

    def round_keys(key):
        seen["programs"] += 1
        return real_keys(key)

    def decode_block(params, cache, tokens, keys, *a, **kw):
        seen["issues"].append((isinstance(keys, jax.Array),
                               tuple(keys.shape), seen["splits"],
                               seen["programs"]))
        return real_block(params, cache, tokens, keys, *a, **kw)

    monkeypatch.setattr(jax.random, "split", split)
    monkeypatch.setattr(b, "_admit", admit)
    monkeypatch.setattr(engine, "round_keys", round_keys)
    monkeypatch.setattr(engine, "decode_block", decode_block)
    b.submit(Request("b", [4, 5], max_new_tokens=8, temperature=0.8))
    b.step()
    (on_device, shape, splits, programs), = seen["issues"]
    assert on_device
    assert shape == (engine.decode_block_len, 2)
    assert splits == 0 and programs == 1


# tokens of PR 29's tree, whose slot-keyed programs were separate bodies
# (`_decode_block_slot_impl`, `_verify_slot_impl` and their `_mixed_impl`
# wrappers): a slot-keyed stream is a function of (base key, position)
# alone, so the one pin holds with and without speculation and the lane
_SLOT_PINS = {"a": [39, 6, 173, 138, 36, 251, 59, 3, 43, 189, 205, 79, 254,
                    56],
              "b": [133, 67, 42, 82, 179, 156, 215, 111, 130, 185]}


@pytest.mark.parametrize("mixed", [False, True])
@pytest.mark.parametrize("spec_len", [0, 3])
def test_slot_keyed_streams_equal_parent(tiny_model_kwargs, mixed, spec_len):
    """Two sampled requests admitted in different rounds under
    ``key_schedule: slot`` draw the tokens the parent's twin bodies drew."""
    cfg, engine = _engine(tiny_model_kwargs, decode_block_len=4,
                          prefill_chunk=8, spec_len=spec_len,
                          key_schedule="slot", mixed_dispatch=mixed)
    b = ContinuousBatcher(engine, _params(cfg, engine), seed=7)
    b.submit(Request("a", list(range(5, 25)), max_new_tokens=14,
                     temperature=0.9, top_k=40))
    b.step()
    b.step()
    b.submit(Request("b", [9, 10, 11, 9, 10, 11, 9, 10], max_new_tokens=10,
                     temperature=1.3, top_k=8, top_p=0.95))
    res = b.run()
    assert {u: r.tokens for u, r in res.items()} == _SLOT_PINS


class _PoisonOnDemand:
    """Dispatch hooks that poison a round only while ``on`` is set."""
    on = False

    def before_dispatch(self, kind, slots):
        pass

    def poison_logits(self, kind):
        return self.on


@pytest.mark.parametrize("family", ["round", "slot", "mixed"])
@pytest.mark.parametrize("kind", ["decode_block", "verify"])
def test_round_program_table_name_and_record(tiny_model_kwargs, monkeypatch,
                                             kind, family):
    """One body, one table, one record, for each round program under each
    family of options: (a) the table holds what was asked for and a
    poisoned build only once a hook asks; (b) the lowered module is named
    after the one body whatever the schedule (benchmarks/stats.py finds
    the decode program by ``_decode_block_impl``); (c) the record's fields
    are None exactly where the options produce nothing."""
    hooks = _PoisonOnDemand()
    rh = family != "round"
    cfg, eng = _engine(
        tiny_model_kwargs, spec_len=3 if kind == "verify" else 0,
        decode_block_len=4, prefill_chunk=8, return_hidden=rh, hooks=hooks,
        mixed_dispatch=family == "mixed",
        key_schedule="round" if family == "round" else "slot")
    params = _params(cfg, eng)
    assert eng._programs == {}
    seen = {}
    build = eng._program

    def program(k, poison=False, dev_tokens=False):
        assert not dev_tokens  # host tokens ride in the packed operand
        def run(*args):  # the cache is donated: keep the shapes
            seen[k, poison] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
            return build(k, poison)(*args)
        return run

    monkeypatch.setattr(eng, "_program", program)
    n = eng.slots
    rows = (np.full(n, -1, np.int32), np.array([4, 0], np.int32),
            np.zeros(n, np.float32), np.zeros(n, np.int32),
            np.ones(n, np.float32))
    keys = {"round": (jax.random.split(jax.random.PRNGKey(1), 4)
                      if kind == "decode_block" else jax.random.PRNGKey(1))
            }.get(family, np.zeros((n, 2), np.uint32))

    def one_round(cache):
        cache = eng.insert(cache, eng.prefill(params, [1, 2, 3, 4, 5])[0],
                           0, 5)
        if kind == "verify":
            return eng.verify(params, cache,
                              np.array([[6, 7, 8, 9], [0] * 4], np.int32),
                              keys, *rows)
        return eng.decode_block(params, cache, np.array([6, 0], np.int32),
                                keys, *rows)

    r = one_round(eng.init_cache())
    # (a) built by the first round that asked, and nothing else
    assert set(eng._programs) == {(kind, False, False)}
    if kind == "decode_block":
        with pytest.raises(ValueError, match="spec_len"):
            eng.verify(params, r.cache, np.zeros((n, 1), np.int32), keys,
                       *rows)
    # (c) fixed fields, None where the options produce nothing
    assert (r.accepted is None) == (kind == "decode_block")
    assert (r.next_tok is None) == (family == "round")
    assert (r.hidden is None) == (not rh)
    assert (r.lane is None) == (family != "mixed")
    assert 1 <= int(np.asarray(r.counts)[0]) <= 4
    assert int(np.asarray(r.counts)[1]) == 0
    if r.hidden is not None:
        assert r.hidden.shape == (n, cfg.model.hidden_size)
    if r.next_tok is not None:
        c = int(np.asarray(r.counts)[0])
        assert int(np.asarray(r.next_tok)[0]) == \
            int(np.asarray(r.tokens)[0, c - 1])
    if r.lane is not None:
        out, hid = r.lane  # an idle lane: its shapes, not its values
        assert out.shape[0] == eng.dp_size and hid.shape[0] == eng.dp_size
    # (b) the module's name is the one body's
    text = build(kind).lower(*seen[kind, False]).as_text()
    body = "_verify_impl" if kind == "verify" else "_decode_block_impl"
    assert f"module @jit_{body} " in text.split("\n", 1)[0]
    # (a) a poisoned build exists only once chaos asks; its tokens are
    # defined (the sampler's non-finite gate)
    hooks.on = True
    p = one_round(eng.init_cache())
    assert set(eng._programs) == {(kind, False, False), (kind, True, False)}
    assert (kind, True) in seen
    toks = np.asarray(p.tokens)
    assert ((toks >= 0) & (toks < cfg.model.vocab_size)).all()
    assert int(np.asarray(p.counts)[0]) >= 1


# --------------------------------------------------------------------------- #
# (d) checkpoint -> engine round trip
# --------------------------------------------------------------------------- #


def test_checkpoint_roundtrip_into_engine(tiny_model_kwargs, tmp_path):
    """Save from an UNEVEN pp=3 training topology (padded stacked layer
    rows), params-only restore into a pp=1 engine with layout remap, and
    decode: the loaded weights must equal the plain-layout init bit-for-bit
    and generate identically to using them directly."""
    cfg3 = make_config(tiny_model_kwargs, pp=3, seq=32)
    topo3 = topology_from_config(cfg3)
    params3, opt3 = ts.init_state(cfg3, topo3)
    L = cfg3.model.num_hidden_layers
    mgr = ckpt.CheckpointManager(str(tmp_path / "c"))
    mgr.save(7, params3, opt3, trained_tokens=1234, layout=(L, 3))
    mgr.close()

    icfg, engine = _engine(tiny_model_kwargs)
    like = jax.eval_shape(partial(llama.init_params, m=icfg.model),
                          jax.random.PRNGKey(0))
    shardings = named_shardings(engine.topo,
                                llama.param_pspecs(icfg.model))
    like = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        like, shardings)
    loaded, step, tokens = ckpt.CheckpointManager(
        str(tmp_path / "c")).load_params(like, layout=(L, 1))
    assert (step, tokens) == (7, 1234)

    # same seed in the plain pp=1 layout == the remapped restore
    direct = _params(icfg, engine, seed=cfg3.training.seed)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(direct)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    req = [Request("g", [9, 8, 7], max_new_tokens=8)]
    got = ContinuousBatcher(engine, loaded).run(req)["g"].tokens
    want = ContinuousBatcher(engine, direct).run(req)["g"].tokens
    assert got == want


def test_generate_cli_end_to_end_from_checkpoint(tiny_model_kwargs, tmp_path,
                                                 capsys):
    """The acceptance-criteria path verbatim: save with checkpoint.py, run
    ``tools/generate.py --load-path`` in-process, get tokens out."""
    from picotron_tpu.tools import generate

    cfg = make_config(tiny_model_kwargs, seq=32)
    topo = topology_from_config(cfg)
    params, opt_state = ts.init_state(cfg, topo)
    mgr = ckpt.CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(3, params, opt_state, trained_tokens=99,
             layout=(cfg.model.num_hidden_layers, 1))
    mgr.close()
    cfg_path = str(tmp_path / "cfg.json")
    cfg.to_json(cfg_path)

    rc = generate.main([
        "--config", cfg_path, "--load-path", str(tmp_path / "ckpt"),
        "--prompt-ids", "4,5,6", "--prompt-ids", "7,8",
        "--max-new-tokens", "6", "--max-seq-len", "64", "--slots", "2"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "loaded step 3" in out
    assert "[req0]" in out and "[req1]" in out


# --------------------------------------------------------------------------- #
# the write/attend seam: a layer index on the stacked leaves
# --------------------------------------------------------------------------- #


def _stacked_cache(rng, L, B, T, H, D, quantized):
    """Random stacked leaves as ``init_cache`` lays them out: as many heads
    to a row as fill its lanes, a scale a head."""
    pack = kv_cache.pack_factor(D, H)

    def leaf():
        return jnp.asarray(rng.normal(size=(L, B, T, H, D)), jnp.bfloat16)

    if not quantized:
        return {n: kv_cache.pack_heads(leaf(), pack) for n in "kv"}
    (qk, ks), (qv, vs) = (kv_cache.quantize_kv(leaf()) for _ in "kv")
    return {"k": kv_cache.pack_heads(qk, pack),
            "v": kv_cache.pack_heads(qv, pack), "k_scale": ks, "v_scale": vs}


# kv heads x head size: (2, 8) keeps a head a row (128 lanes are not 16
# heads of 8 when there are 2); heads of 64 lie two to a row, heads of 32
# four; a head of 128 is a row
SEAM_HEADS = {"d8": (2, 8), "d64": (4, 64), "d32": (4, 32), "d128": (2, 128)}


def test_pack_factor_reads_head_size_and_local_heads():
    assert [kv_cache.pack_factor(d, h) for h, d in SEAM_HEADS.values()] \
        == [1, 2, 4, 1]
    # SmolLM, Mistral; tp leaving an odd head count falls back to a row a head
    assert kv_cache.pack_factor(64, 32) == 2
    assert kv_cache.pack_factor(128, 8) == 1
    assert kv_cache.pack_factor(64, 1) == kv_cache.pack_factor(64, 3) == 1
    assert kv_cache.pack_factor(96, 4) == kv_cache.pack_factor(256, 4) == 1


SEAM_SHAPES = ["decode", "block", "block_of_one", "open_block",
               "open_block_of_one", "shut_block", "shut_block_of_one",
               "verify", "ragged_verify"]


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("shape,heads", [
    (s, h) for h in ("d8", "d64") for s in SEAM_SHAPES] + [
    (s, "d32") for s in ("decode", "open_block", "ragged_verify")] + [
    ("decode", "d128")])
def test_layer_indexed_seam_matches_per_layer(shape, quantized, heads):
    """``cache_write`` / ``attend`` addressed ``[layer, ...]`` into the
    STACKED leaves (what the engine's layer scan carries) hold bit for bit
    the bytes a row-by-row placement puts there, and attend like the
    per-layer ``decode_attention`` on that layer's slice — for the three
    write shapes, the ragged ``draft_valid`` mask, the one-slot ``slot`` /
    ``gate`` addressing of a prefill chunk AT ANY WIDTH (a chunk of one
    token is still its slot's, and still gated), and int8 storage with its
    scales — and touch no other layer and no other slot. With heads
    narrower than a lane row the leaves hold ``pack_factor`` heads a row:
    the same bytes land, and the attention over whole rows is the attention
    over a head a row."""
    rng = np.random.default_rng(7)
    L, B, T, layer = 3, 3, 16, 1
    H, D = SEAM_HEADS[heads]
    cache = _stacked_cache(rng, L, B, T, H, D, quantized)
    assert cache["k"].shape[-1] == kv_cache.pack_factor(D, H) * D
    addr, valid, gate = {}, None, True
    if shape == "decode":
        slots, s, pos = range(B), 1, [6, 3, 0]
    elif "block" in shape:
        slots, s, pos = [2], (1 if shape.endswith("of_one") else 4), [5]
        addr = {"slot": jnp.asarray(2, jnp.int32)}
        if shape.startswith(("open", "shut")):
            gate = shape.startswith("open")
            addr["gate"] = jnp.asarray(gate)
    else:
        slots, s, pos = range(B), 4, [6, 14, 2]  # slot 1 runs off the end
        if shape == "ragged_verify":
            valid = [4, 1, 2]
            addr = {"draft_valid": jnp.asarray(valid, jnp.int32)}
    b = len(slots)
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, s, H, D)), jnp.bfloat16)
                    for _ in "kv")
    q = jnp.asarray(rng.normal(size=(b, s, 2 * H, D)), jnp.bfloat16)
    f32 = lambda x: np.array(x.astype(jnp.float32))

    got = kv_cache.cache_write({**cache, **addr}, k_new, v_new,
                               jnp.asarray(pos, jnp.int32), layer)
    # the oracle: every live row placed one at a time, nothing else touched
    new = {"k": k_new, "v": v_new}
    if quantized:
        (new["k"], new["k_scale"]), (new["v"], new["v_scale"]) = (
            kv_cache.quantize_kv(x) for x in (k_new, v_new))
    heads_of = lambda n, a: (kv_cache.unpack_heads(a, D) if n in "kv"
                             else a)
    want = {n: f32(heads_of(n, a)) for n, a in cache.items()}
    for n, rows in new.items():
        for i, slot in enumerate(slots):
            live = s if valid is None else valid[i]
            for j in range(live if gate else 0):
                if pos[i] + j < T:
                    want[n][layer, slot, pos[i] + j] = f32(rows)[i, j]
    for n in cache:
        assert got[n].shape == cache[n].shape
        np.testing.assert_array_equal(f32(heads_of(n, got[n])), want[n])
    lengths = jnp.asarray(pos, jnp.int32) + s
    k, v = (heads_of(n, got[n])[layer, jnp.asarray(list(slots))]
            for n in "kv")
    if quantized:
        k, v = (kv_cache.dequantize_kv(
            x, got[n][layer, jnp.asarray(list(slots))], jnp.float32)
            for x, n in ((k, "k_scale"), (v, "v_scale")))
    out = f32(kv_cache.attend(q, {**got, **addr}, lengths, 0.3, layer))
    pack = cache["k"].shape[-1] // D
    np.testing.assert_array_equal(out, f32(kv_cache.decode_attention(
        q, kv_cache.pack_heads(k, pack), kv_cache.pack_heads(v, pack),
        lengths, 0.3)))
    # a head a row: the same products, summed with zeros among them
    np.testing.assert_allclose(
        out, f32(kv_cache.decode_attention(q, k, v, lengths, 0.3)),
        rtol=0, atol=2 ** -6)
