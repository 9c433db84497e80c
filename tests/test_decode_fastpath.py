"""Decode fast path (ISSUE-3): blocked decode, int8 KV cache, chunked
prefill, and the batcher scheduling fix.

Acceptance surface:
- ``decode_block`` with block_len ∈ {1, 4, 8} produces EXACTLY the token
  streams of the per-token ``decode_step`` loop — EOS and budget stops
  mid-block included — on tp=1 and a tp=2 dryrun mesh, with
  ≤ ceil(N/block_len) + O(1) decode dispatches for N tokens;
- int8-cache greedy decode tracks the fp32-cache oracle (pinned max-abs
  logits bound + token-match rate), and the int8 cache (scales included)
  measures ≤ ~55% of the bf16 cache bytes;
- chunked prefill matches the one-shot bucketed prefill (allclose K/V
  blocks, identical last-token argmax) for prompts spanning 1–3 chunks,
  ragged final chunks included;
- a slot freed by a deadline timeout is refilled in the SAME scheduler
  round (expire-before-admit), not the next.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import KV_PACK, make_config, with_heads
from picotron_tpu.inference import (
    ContinuousBatcher,
    InferenceEngine,
    Request,
)
from picotron_tpu.inference import kv_cache
from picotron_tpu.models import llama

MAX_LEN = 96

# int8 acceptance knobs: bound on the first post-prefill decode step's
# logits error vs the fp32 cache (measured ~2e-3 on the tiny model; 25x
# margin), and the greedy token-match rate over a 24-token stream
INT8_LOGITS_ATOL = 0.05
INT8_TOKEN_MATCH_RATE = 0.9


def _engine(tiny_model_kwargs, tp=1, slots=2, **kw):
    cfg = make_config(tiny_model_kwargs, tp=tp, seq=MAX_LEN)
    return cfg, InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN, **kw)


def _params(cfg, engine, seed=0):
    p = jax.jit(lambda k: llama.init_params(k, cfg.model))(
        jax.random.PRNGKey(seed))
    return engine.shard_params(p)


def _per_token_reference(engine, params, prompt, max_new, eos_id=None):
    """The PR-1 per-token serving loop, written out against decode_step:
    one dispatch + one host sync per token, host-side EOS/budget checks.
    The greedy oracle every blocked run must reproduce bit-for-bit."""
    cache = engine.init_cache()
    kv, logits = engine.prefill(params, prompt)
    cache = engine.insert(cache, kv, 0, len(prompt))
    n = engine.slots
    toks = [int(np.argmax(np.asarray(logits)[0]))]
    temp = np.zeros(n, np.float32)
    top_k = np.zeros(n, np.int32)
    top_p = np.ones(n, np.float32)
    key = jax.random.PRNGKey(0)
    budget = min(max_new, engine.max_seq_len - len(prompt))
    while len(toks) < budget and (eos_id is None or toks[-1] != eos_id):
        feed = np.zeros(n, np.int32)
        feed[0] = toks[-1]
        key, sub = jax.random.split(key)
        cache, out, _ = engine.decode_step(params, cache, feed, sub,
                                           temp, top_k, top_p)
        toks.append(int(np.asarray(out)[0]))
    return toks


# --------------------------------------------------------------------------- #
# blocked decode == per-token loop
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("block_len", [1, 4, 8])
def test_decode_block_matches_per_token_loop(tiny_model_kwargs, tp,
                                             block_len):
    """Greedy streams through the blocked batcher — budgets that stop
    mid-block (17 and 6 tokens against blocks of 4/8) — must equal the
    explicit per-token decode_step loop token for token."""
    cfg, engine = _engine(tiny_model_kwargs, tp=tp,
                          decode_block_len=block_len)
    params = _params(cfg, engine)
    reqs = [Request("a", [1, 2, 3, 4, 5], max_new_tokens=17),
            Request("b", [9, 8, 7], max_new_tokens=6)]
    got = ContinuousBatcher(engine, params).run(reqs)
    for r in reqs:
        want = _per_token_reference(engine, params, r.prompt,
                                    r.max_new_tokens)
        assert got[r.uid].tokens == want, (r.uid, block_len, tp)
        assert got[r.uid].finish_reason == "length"


@pytest.mark.parametrize("block_len", [4, 8])
def test_decode_block_eos_mid_block(tiny_model_kwargs, block_len):
    """A slot hitting EOS mid-block goes inactive on device: the stream
    ends AT the EOS token (no post-EOS garbage), identical to the
    per-token loop, and the queued request behind it still completes."""
    cfg, engine = _engine(tiny_model_kwargs, slots=1,
                          decode_block_len=block_len)
    params = _params(cfg, engine)
    prompt = [5, 6, 7, 8]
    free = ContinuousBatcher(engine, params).run(
        [Request("f", prompt, max_new_tokens=12)])["f"]
    eos = free.tokens[5]  # forces a stop 6 tokens in — mid-block for both
    assert eos not in free.tokens[:5], "pick a different seed/prompt"
    res = ContinuousBatcher(engine, params).run([
        Request("x", prompt, max_new_tokens=12, eos_id=eos),
        Request("y", [3, 1, 4], max_new_tokens=5),
    ])
    assert res["x"].finish_reason == "eos"
    assert res["x"].tokens == free.tokens[:6]
    assert res["x"].tokens == _per_token_reference(
        engine, params, prompt, 12, eos_id=eos)
    assert res["y"].finish_reason == "length"
    assert len(res["y"].tokens) == 5


def test_decode_block_stochastic_key_chain(tiny_model_kwargs):
    """Sampled (temperature > 0) streams pin the PRNG plumbing the greedy
    tests can't see: the batcher splits one key per in-block step in chain
    order, so block_len ∈ {1, 4} and an explicit decode_step loop driving
    the SAME split chain must all draw identical tokens — including a
    finish mid-block (14 = 1 prefill token + 13 decode steps vs blocks
    of 4)."""
    cfg, eng1 = _engine(tiny_model_kwargs, decode_block_len=1)
    _, eng4 = _engine(tiny_model_kwargs, decode_block_len=4)
    params = _params(cfg, eng1)
    req = Request("r", [2, 4, 6, 8], max_new_tokens=14,
                  temperature=0.8, top_k=5, top_p=0.9)
    got1 = ContinuousBatcher(eng1, params, seed=3).run([req])["r"].tokens
    got4 = ContinuousBatcher(eng4, params, seed=3).run([req])["r"].tokens

    # the batcher's chain, written out against decode_step: one split for
    # the admit-time draw, then one split per decode round
    key = jax.random.PRNGKey(3)
    cache = eng1.init_cache()
    kv, logits = eng1.prefill(params, req.prompt)
    cache = eng1.insert(cache, kv, 0, len(req.prompt))
    n = eng1.slots
    temp = np.zeros(n, np.float32)
    top_k = np.zeros(n, np.int32)
    top_p = np.ones(n, np.float32)
    temp[0], top_k[0], top_p[0] = req.temperature, req.top_k, req.top_p
    key, sub = jax.random.split(key)
    from picotron_tpu.inference import sampling
    want = [int(sampling.sample(logits, sub, temp[:1], top_k[:1],
                                top_p[:1])[0])]
    while len(want) < req.max_new_tokens:
        feed = np.zeros(n, np.int32)
        feed[0] = want[-1]
        key, sub = jax.random.split(key)
        cache, out, _ = eng1.decode_step(params, cache, feed, sub,
                                         temp, top_k, top_p)
        want.append(int(np.asarray(out)[0]))
    assert got1 == want
    assert got4 == want


@pytest.mark.parametrize("block_len", [1, 4, 8])
def test_decode_dispatch_count(tiny_model_kwargs, block_len):
    """N tokens must cost ≤ ceil(N/block_len) + O(1) decode dispatches —
    the host-sync amortization the block exists for."""
    cfg, engine = _engine(tiny_model_kwargs, slots=2,
                          decode_block_len=block_len)
    params = _params(cfg, engine)
    n_new = 24
    b = ContinuousBatcher(engine, params)
    res = b.run([Request("a", [1, 2, 3], max_new_tokens=n_new)])["a"]
    assert len(res.tokens) == n_new
    assert b.generated_tokens == n_new
    assert b.decode_dispatches <= math.ceil(n_new / block_len) + 1
    assert b.prefill_dispatches == 1


# --------------------------------------------------------------------------- #
# int8 KV cache
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("heads", ["d8", "d64", "d32"])
@pytest.mark.parametrize("tp", [1, 2])
def test_int8_cache_tracks_fp32_oracle(tiny_model_kwargs, tp, heads):
    """Greedy decode from the int8 cache must track the fp32-cache oracle:
    first-step logits within INT8_LOGITS_ATOL, ≥ INT8_TOKEN_MATCH_RATE of
    24 greedy tokens identical (tp=2 shards the scale tensors' head axis
    alongside K/V). With two or four heads a row the int8 rows lie packed
    and the scales stay one a head."""
    tiny_model_kwargs = with_heads(tiny_model_kwargs, heads)
    cfg, eng_f = _engine(tiny_model_kwargs, tp=tp)
    _, eng_q = _engine(tiny_model_kwargs, tp=tp, cache_dtype="int8")
    assert eng_q.quantized and eng_q.kv_pack == KV_PACK[heads][tp - 1]
    shapes = jax.eval_shape(eng_q.init_cache)
    assert shapes["k"].dtype == jnp.int8 and shapes["k_scale"].shape \
        == shapes["k"].shape[:3] + (cfg.model.num_key_value_heads,)
    params = _params(cfg, eng_f)
    prompt = list(range(1, 9))

    # per-step logits bound: same prompt parked in both caches, one step
    kv_f, lg_f = eng_f.prefill(params, prompt)
    kv_q, lg_q = eng_q.prefill(params, prompt)
    np.testing.assert_array_equal(np.asarray(lg_f), np.asarray(lg_q))
    c_f = eng_f.insert(eng_f.init_cache(), kv_f, 0, len(prompt))
    c_q = eng_q.insert(eng_q.init_cache(), kv_q, 0, len(prompt))
    n = eng_f.slots
    feed = np.zeros(n, np.int32)
    feed[0] = int(np.argmax(np.asarray(lg_f)[0]))
    args = (feed, jax.random.PRNGKey(0), np.zeros(n, np.float32),
            np.zeros(n, np.int32), np.ones(n, np.float32))
    _, _, lo_f = eng_f.decode_step(params, c_f, *args)
    _, _, lo_q = eng_q.decode_step(params, c_q, *args)
    err = float(np.max(np.abs(np.asarray(lo_f)[0] - np.asarray(lo_q)[0])))
    assert err < INT8_LOGITS_ATOL, err

    # stream-level token match rate
    req = [Request("r", prompt, max_new_tokens=24)]
    toks_f = ContinuousBatcher(eng_f, params).run(req)["r"].tokens
    toks_q = ContinuousBatcher(eng_q, params).run(req)["r"].tokens
    match = np.mean([a == b for a, b in zip(toks_f, toks_q)])
    assert match >= INT8_TOKEN_MATCH_RATE, (match, toks_f, toks_q)


def test_int8_cache_halves_bytes():
    """int8 cache bytes (scales included) ≤ 55% of the bf16 cache at the
    production head_dim 64 — the ~2x slots-or-context headroom claim."""
    from picotron_tpu.config import ModelConfig

    m = ModelConfig(num_hidden_layers=2, num_attention_heads=4,
                    num_key_value_heads=4, hidden_size=256,
                    vocab_size=128, dtype="bfloat16")
    assert m.head_dim == 64
    bf16 = kv_cache.cache_bytes(kv_cache.init_cache(m, 4, 128))
    int8 = kv_cache.cache_bytes(
        kv_cache.init_cache(m, 4, 128, quantized=True))
    assert int8 <= 0.55 * bf16, (int8, bf16)
    # and the quantizer round-trips within one scale step of exact
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 4, 64), jnp.float32)
    q, s = kv_cache.quantize_kv(x)
    back = kv_cache.dequantize_kv(q, s, jnp.float32)
    step = np.asarray(s)[..., None] / 2 + 1e-7
    assert np.all(np.abs(np.asarray(back) - np.asarray(x)) <= step)


# --------------------------------------------------------------------------- #
# chunked prefill
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("tp,n_tokens,heads", [
    (tp, n, "d8") for n in (10, 16, 23, 32, 41) for tp in (1, 2)] + [
    (1, 23, "d64"), (2, 41, "d64"), (1, 41, "d64gqa"), (2, 23, "d64gqa"),
    (1, 32, "d32"), (1, 23, "d128")])
def test_chunked_prefill_matches_one_shot(tiny_model_kwargs, tp, n_tokens,
                                          heads):
    """prefill_chunked (chunk width 16; prompts spanning 1–3 chunks, ragged
    finals included) must reproduce the bucketed one-shot prefill: K/V rows
    allclose, lengths equal, last-token logits allclose with identical
    argmax — with a head a row of the cache and with two or four."""
    cfg, engine = _engine(with_heads(tiny_model_kwargs, heads), tp=tp,
                          prefill_chunk=16)
    params = _params(cfg, engine)
    prompt = [(7 * i + 3) % cfg.model.vocab_size for i in range(n_tokens)]

    kv, lg_ref = engine.prefill(params, prompt)
    ref = engine.insert(engine.init_cache(), kv, 1, n_tokens)
    chk, lg_chk = engine.prefill_chunked(params, engine.init_cache(),
                                         prompt, 1)
    np.testing.assert_array_equal(np.asarray(ref["lengths"]),
                                  np.asarray(chk["lengths"]))
    for name in ("k", "v"):
        a = np.asarray(ref[name])[:, 1, :n_tokens]
        b = np.asarray(chk[name])[:, 1, :n_tokens]
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lg_ref), np.asarray(lg_chk),
                               rtol=1e-4, atol=1e-4)
    assert (np.argmax(np.asarray(lg_ref)[0])
            == np.argmax(np.asarray(lg_chk)[0]))


def test_chunked_prefill_ragged_cache_window(tiny_model_kwargs):
    """max_seq_len NOT a multiple of prefill_chunk: the final chunk's write
    window would overrun the cache and dynamic_update_slice would CLAMP it
    onto earlier prompt rows — the slide-back path must instead reproduce
    the one-shot prefill exactly (regression: silent K/V corruption)."""
    cfg = make_config(tiny_model_kwargs, seq=24)
    engine = InferenceEngine(cfg, slots=2, max_seq_len=24, prefill_chunk=16)
    params = _params(cfg, engine)
    prompt = [(5 * i + 2) % cfg.model.vocab_size for i in range(20)]

    kv, lg_ref = engine.prefill(params, prompt)
    ref = engine.insert(engine.init_cache(), kv, 0, len(prompt))
    chk, lg_chk = engine.prefill_chunked(params, engine.init_cache(),
                                         prompt, 0)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(ref[name])[:, 0, :len(prompt)],
            np.asarray(chk[name])[:, 0, :len(prompt)],
            rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lg_ref), np.asarray(lg_chk),
                               rtol=1e-4, atol=1e-4)
    assert (np.argmax(np.asarray(lg_ref)[0])
            == np.argmax(np.asarray(lg_chk)[0]))


@pytest.mark.parametrize("heads", ["d8", "d64"])
@pytest.mark.parametrize("case", ["bf16", "int8", "dp2"])
def test_chunk_of_one_token_lands_in_its_own_slot(tiny_model_kwargs, case,
                                                  heads):
    """A prefill chunk ONE token wide (``inference.prefill_chunk: 1`` is a
    legal config) is still a one-slot write: prefilled into slot > 0 beside
    a parked neighbour it leaves the neighbour's rows byte for byte alone
    and parks the same rows and logits as the chunk-4 run — on the dp-
    sharded mesh too, where the non-owner shard's gate must hold at width
    1 (regression: the S == 1 write ignored ``slot`` and ``gate``)."""
    tiny_model_kwargs = with_heads(tiny_model_kwargs, heads)
    kw = {"cache_dtype": "int8"} if case == "int8" else {}
    n_tokens, slot = 9, 3 if case == "dp2" else 1

    def run(chunk):
        cfg = make_config(tiny_model_kwargs, seq=MAX_LEN)
        cfg.inference.dp_size = 2 if case == "dp2" else 1
        engine = InferenceEngine(cfg, slots=4 if case == "dp2" else 2,
                                 max_seq_len=MAX_LEN, prefill_chunk=chunk,
                                 **kw)
        params = _params(cfg, engine)
        V = cfg.model.vocab_size
        parked, _ = engine.prefill_chunked(
            params, engine.init_cache(), [(3 * i + 1) % V for i in range(6)],
            0)
        before = jax.device_get(parked)  # the next call donates ``parked``
        out, logits = engine.prefill_chunked(
            params, parked, [(7 * i + 3) % V for i in range(n_tokens)], slot)
        return before, jax.device_get(out), logits

    parked1, one, lg_one = run(1)
    _, four, lg_four = run(4)
    np.testing.assert_array_equal(one["lengths"], four["lengths"])
    for name in (n for n in one if n != "lengths"):
        a, b = (np.asarray(x[name], np.float32) for x in (one, four))
        np.testing.assert_allclose(a[:, slot, :n_tokens],
                                   b[:, slot, :n_tokens],
                                   rtol=1e-5, atol=1e-5)
        others = [i for i in range(a.shape[1]) if i != slot]
        np.testing.assert_array_equal(
            a[:, others], np.asarray(parked1[name], np.float32)[:, others])
    np.testing.assert_allclose(np.asarray(lg_one), np.asarray(lg_four),
                               rtol=1e-4, atol=1e-4)


def test_cache_dtype_keyword_overrides_config(tiny_model_kwargs):
    """An explicit cache_dtype wins over inference.kv_cache_dtype in BOTH
    directions — int8 on, and back off."""
    cfg = make_config(tiny_model_kwargs, seq=MAX_LEN)
    cfg.inference.kv_cache_dtype = "int8"
    assert InferenceEngine(cfg, max_seq_len=MAX_LEN).quantized
    off = InferenceEngine(cfg, max_seq_len=MAX_LEN, cache_dtype="float32")
    assert not off.quantized and off.cache_dtype == np.dtype(np.float32)


def test_chunked_prefill_through_batcher(tiny_model_kwargs):
    """A prompt above prefill_chunk admits through the chunked path and
    generates the same stream as an engine whose chunk width makes the
    same prompt take the bucketed one-shot path (int8 cache included —
    chunk writes quantize like inserts do)."""
    for extra in ({}, {"cache_dtype": "int8"}):
        cfg, eng_c = _engine(tiny_model_kwargs, prefill_chunk=16, **extra)
        _, eng_b = _engine(tiny_model_kwargs, prefill_chunk=512, **extra)
        params = _params(cfg, eng_c)
        prompt = [(3 * i + 1) % cfg.model.vocab_size for i in range(40)]
        req = [Request("r", prompt, max_new_tokens=8)]
        bc = ContinuousBatcher(eng_c, params)
        chunked = bc.run(req)["r"].tokens
        assert bc.prefill_dispatches == 3  # ceil(40/16)
        bucketed = ContinuousBatcher(eng_b, params).run(req)["r"].tokens
        assert chunked == bucketed, extra


# --------------------------------------------------------------------------- #
# batcher scheduling: expire before admit
# --------------------------------------------------------------------------- #


def test_timeout_slot_refilled_same_round(tiny_model_kwargs):
    """A slot whose request is past deadline at the top of step() must be
    expired AND refilled by the waiting request within that same step —
    the old admit-first order left it idle for a full round."""

    class Clock:
        t = 0.0

        def __call__(self):
            self.t += 1.0
            return self.t

    cfg, engine = _engine(tiny_model_kwargs, slots=1, decode_block_len=2)
    params = _params(cfg, engine)
    b = ContinuousBatcher(engine, params, clock=Clock())
    b.submit(Request("hog", [1, 2, 3], max_new_tokens=64, timeout_s=0.5))
    b.submit(Request("queued", [4, 5, 6], max_new_tokens=4))
    b.step()  # admits hog (deadline already in the past after admit)
    assert b._slots[0] is not None and b._slots[0].req.uid == "hog"
    b.step()  # ONE round: expire hog -> admit queued -> decode queued
    assert "hog" in b._results
    assert b._results["hog"].finish_reason == "timeout"
    s = b._slots[0]
    assert s is not None and s.req.uid == "queued"
    assert len(s.generated) > 0  # queued decoded in the same round
