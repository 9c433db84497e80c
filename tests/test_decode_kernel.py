"""Flash-decode kernel parity: Pallas fused KV-cache attention vs dense.

The flash path (``ops/pallas/decode_attention.py``, selected by
``inference.attend_impl: "flash"``) must be allclose to the dense
whole-window reference (``kv_cache.decode_attention``) everywhere the
engine can reach it — S = 1 blocked decode, S > 1 speculative verify,
B = 1 chunked prefill — for bf16/fp32 AND int8 caches, across ragged
lengths, stale rows beyond the length mask, GQA head groupings down to
nkv = 1, and cache windows that are not a multiple of the KV block. The
kernel runs in Pallas interpret mode here (the CPU tier-1 gate;
``make kernel-smoke`` runs just this file); the same program lowers to
Mosaic on a chip.

Unit tests drive the kernel directly; the engine tests run the full jitted
dispatch (shard_map + layer scan) under both impls and pin identical
generations — the wiring proof that ``attend_impl`` reaches all three call
sites.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_config, with_heads
from picotron_tpu.inference import InferenceEngine, kv_cache
from picotron_tpu.inference.kv_cache import (
    decode_attention,
    dequantize_kv,
    quantize_kv,
)
from picotron_tpu.models import llama
from picotron_tpu.ops.pallas.decode_attention import (
    _pick_block_t,
    flash_decode_attention,
    flash_decode_stacked,
)

MAX_LEN = 96


# --------------------------------------------------------------------------- #
# kernel-level parity (direct calls, interpret mode)
# --------------------------------------------------------------------------- #


def _blocks(rng, B, T, nh, nkv, D, S, dtype, quantized):
    """Random q + cache blocks (+ scales when quantized) and the dense
    reference inputs (the dequantized fp32 view for int8)."""
    q = jnp.asarray(rng.normal(size=(B, S, nh, D)).astype(np.float32))
    k = rng.normal(size=(B, T, nkv, D)).astype(np.float32)
    v = rng.normal(size=(B, T, nkv, D)).astype(np.float32)
    if quantized:
        qk, ks = quantize_kv(jnp.asarray(k))
        qv, vs = quantize_kv(jnp.asarray(v))
        dense_k = dequantize_kv(qk, ks, jnp.float32)
        dense_v = dequantize_kv(qv, vs, jnp.float32)
        return q, (qk, qv, ks, vs), (dense_k, dense_v)
    dt = jnp.dtype(dtype)
    kj, vj = jnp.asarray(k, dt), jnp.asarray(v, dt)
    return q.astype(dt), (kj, vj, None, None), (kj, vj)


def _assert_parity(q, stored, dense_kv, lengths, block_t, tol):
    k, v, ks, vs = stored
    scale = q.shape[-1] ** -0.5
    want = np.asarray(
        decode_attention(q, dense_kv[0], dense_kv[1], lengths, scale),
        np.float32)
    got = np.asarray(
        flash_decode_attention(q, k, v, lengths, scale, k_scale=ks,
                               v_scale=vs, block_t=block_t, interpret=True),
        np.float32)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=tol, atol=tol)
    # fully-masked rows are DEFINED as zeros on the flash path (the dense
    # kernel emits an equally-unconsumed uniform average there)
    assert np.all(got[~live] == 0.0)
    return got


@pytest.mark.parametrize("cache_dtype,tol", [
    ("float32", 1e-5), ("bfloat16", 2e-2), ("int8", 1e-5)])
@pytest.mark.parametrize("S", [1, 4])
def test_flash_matches_dense_decode_and_verify(cache_dtype, S, tol):
    """S=1 decode and S=4 (spec_len+1) verify shapes: ragged lengths
    including a fresh slot (0), an S-length slot, and a full window, on
    the GQA 8q/4kv grouping, for all three cache dtypes."""
    rng = np.random.default_rng(0)
    B, T, nh, nkv, D = 4, 64, 8, 4, 16
    q, stored, dense_kv = _blocks(rng, B, T, nh, nkv, D, S,
                                  cache_dtype, cache_dtype == "int8")
    if cache_dtype == "bfloat16":
        q = q.astype(jnp.bfloat16)
    lengths = jnp.asarray([0, S, 29, T], jnp.int32)
    _assert_parity(q, stored, dense_kv, lengths, 16, tol)


@pytest.mark.parametrize("quantized", [False, True])
def test_flash_matches_dense_chunked_prefill(quantized):
    """The B=1, S=chunk call shape: queries attend over the cache prefix
    plus their own freshly-written block (lengths = start + chunk)."""
    rng = np.random.default_rng(1)
    B, T, nh, nkv, D, S = 1, MAX_LEN, 8, 4, 16, 16
    q, stored, dense_kv = _blocks(rng, B, T, nh, nkv, D, S,
                                  "float32", quantized)
    for length in (S, 40, MAX_LEN):  # first chunk, mid-prompt, full window
        _assert_parity(q, stored, dense_kv,
                       jnp.asarray([length], jnp.int32), 32, 1e-5)


def test_gqa_single_kv_head():
    """nkv=1 (every q head in one group) — the widest grouping the fold
    must handle."""
    rng = np.random.default_rng(2)
    q, stored, dense_kv = _blocks(rng, 2, 32, 4, 1, 8, 1, "float32", True)
    _assert_parity(q, stored, dense_kv, jnp.asarray([5, 32], jnp.int32),
                   8, 1e-5)


def test_window_not_multiple_of_block():
    """T=40 with a requested block of 16 halves to 8 (the static DMA slice
    must tile the window); ragged lengths hit the partial-live block."""
    assert _pick_block_t(40, 16) == 8
    # wide chunked-prefill query groups trade KV-block depth for rows so
    # the fp32 score tile stays inside the VMEM budget
    assert _pick_block_t(4096, 256, rows=4096) == 64
    rng = np.random.default_rng(3)
    q, stored, dense_kv = _blocks(rng, 3, 40, 8, 4, 16, 1, "float32", False)
    _assert_parity(q, stored, dense_kv, jnp.asarray([1, 23, 40], jnp.int32),
                   16, 1e-5)


def test_lengths_past_window_clamped():
    """At the cache-window edge the engine's write-then-attend convention
    can pass lengths = pos + S > T (the scatter dropped the OOB rows); the
    block walk must clamp to the window instead of DMA'ing past it, and
    still match dense (whose mask absorbs the same case)."""
    rng = np.random.default_rng(6)
    q, stored, dense_kv = _blocks(rng, 2, 32, 8, 4, 16, 2, "float32", False)
    _assert_parity(q, stored, dense_kv, jnp.asarray([33, 34], jnp.int32),
                   8, 1e-5)


def test_stale_rows_beyond_mask_invisible():
    """Rows past ``lengths`` (a speculative rollback's rejected drafts, a
    freed slot's leftovers) are poisoned with huge values; the flash output
    must not move — the mask, not luck, keeps them out."""
    rng = np.random.default_rng(4)
    B, T, nh, nkv, D = 2, 48, 8, 4, 16
    q, (k, v, _, _), _ = _blocks(rng, B, T, nh, nkv, D, 1, "float32", False)
    lengths = jnp.asarray([7, 31], jnp.int32)
    scale = D ** -0.5
    clean = flash_decode_attention(q, k, v, lengths, scale, block_t=16,
                                   interpret=True)
    rows = np.arange(T)[None, :, None, None] >= np.asarray(lengths)[
        :, None, None, None]
    poison = jnp.where(rows, 1e4, 0.0).astype(k.dtype)
    dirty = flash_decode_attention(q, k + poison, v + poison, lengths,
                                   scale, block_t=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.parametrize("D", [16, 64])
def test_flash_path_never_materializes_dequantized_cache(monkeypatch, D):
    """The int8 flash attend must read int8 bytes + scales inside the
    kernel — if it ever routed through ``dequantize_kv`` (the dense path's
    whole-block fp32 materialization) this raises. Heads of 64 lie two to
    a row of the cache: flash takes the head-a-row view of the same bytes."""
    rng = np.random.default_rng(5)
    q, (k, v, ks, vs), (dk, dv) = _blocks(rng, 2, 32, 8, 4, D, 1,
                                          "float32", True)
    pack = kv_cache.pack_factor(D, 4)
    assert pack == {16: 1, 64: 2}[D]
    cache = {"k": kv_cache.pack_heads(k, pack)[None],
             "v": kv_cache.pack_heads(v, pack)[None], "k_scale": ks[None],
             "v_scale": vs[None]}  # stacked leaves of a one-layer cache
    lengths = jnp.asarray([9, 20], jnp.int32)
    want = np.asarray(kv_cache.attend(q, cache, lengths, 0.25, 0,
                                      impl="dense"))

    def boom(*a, **kw):
        raise AssertionError("flash attend materialized a dequantized copy")

    monkeypatch.setattr(kv_cache, "dequantize_kv", boom)
    got = np.asarray(kv_cache.attend(q, cache, lengths, 0.25, 0,
                                     impl="flash"))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# the block walk: many KV blocks agree with one whole-window block and dense
# --------------------------------------------------------------------------- #


def _paged_blocks(rng, B, maxp, plen, nkv, D, S, nh, quantized):
    """A page pool + shuffled block tables + the dense gathered window."""
    P = 2 + B * maxp
    q = jnp.asarray(rng.normal(size=(B, S, nh, D)).astype(np.float32))
    pk = rng.normal(size=(P, plen, nkv, D)).astype(np.float32)
    pv = rng.normal(size=(P, plen, nkv, D)).astype(np.float32)
    tables = jnp.asarray(
        rng.permutation(np.arange(1, P))[: B * maxp].reshape(B, maxp),
        jnp.int32)
    if quantized:
        qk, ks = quantize_kv(jnp.asarray(pk))
        qv, vs = quantize_kv(jnp.asarray(pv))
        dk = dequantize_kv(qk, ks, jnp.float32)
        dv = dequantize_kv(qv, vs, jnp.float32)
        stored = (qk, qv, ks, vs)
    else:
        stored = (jnp.asarray(pk), jnp.asarray(pv), None, None)
        dk, dv = stored[0], stored[1]
    gather = lambda pool: pool[tables].reshape(B, maxp * plen, *pool.shape[2:])
    return q, stored, (gather(dk), gather(dv)), tables


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("T,block_t,lengths", [
    (16, 16, [16, 7]),        # single block: the whole window is one DMA
    (48, 16, [48, 33]),       # odd block count (3)
    (40, 16, [1, 23]),        # T % block_t != 0 (block halves to 8)
    (32, 8, [0, 0]),          # nothing live: zero iterations, zeros out
    (32, 8, [0, 29]),         # fresh slot riding next to a live one
])
def test_block_walk_matches_one_block_and_dense_contiguous(
        T, block_t, lengths, quantized):
    """The length-bounded walk over several KV blocks must be allclose to
    the same call with the whole window as ONE block and to dense, across
    the nasty window shapes and int8 scales."""
    rng = np.random.default_rng(10)
    B, nh, nkv, D, S = 2, 8, 4, 16, 1
    q, stored, dense_kv = _blocks(rng, B, T, nh, nkv, D, S,
                                  "float32", quantized)
    lengths = jnp.asarray(lengths, jnp.int32)
    walked = _assert_parity(q, stored, dense_kv, lengths, block_t, 1e-5)
    k, v, ks, vs = stored
    one_block = np.asarray(flash_decode_attention(
        q, k, v, lengths, q.shape[-1] ** -0.5, k_scale=ks, v_scale=vs,
        block_t=T, interpret=True))
    np.testing.assert_allclose(walked, one_block, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
@pytest.mark.parametrize("maxp,plen,lengths", [
    (1, 16, [16, 5]),         # single page per slot
    (3, 8, [24, 17]),         # odd page count
    (4, 8, [0, 31]),          # fresh slot + nearly-full slot
])
def test_block_walk_matches_dense_paged(
        maxp, plen, lengths, quantized):
    """The paged walk (one block per pool page through the block table)
    is allclose to the dense gathered-window reference, fp32 and int8
    pools, and a fresh slot comes out as zeros."""
    rng = np.random.default_rng(11)
    B, nh, nkv, D, S = 2, 8, 4, 16, 1
    q, stored, dense_kv, tables = _paged_blocks(
        rng, B, maxp, plen, nkv, D, S, nh, quantized)
    lengths = jnp.asarray(lengths, jnp.int32)
    scale = q.shape[-1] ** -0.5
    k, v, ks, vs = stored
    want = np.asarray(
        decode_attention(q, dense_kv[0], dense_kv[1], lengths, scale))
    got = np.asarray(flash_decode_attention(
        q, k, v, lengths, scale, k_scale=ks, v_scale=vs,
        block_tables=tables, interpret=True))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    assert np.all(got[~live] == 0.0)


def test_block_walk_verify_shape():
    """The S>1 verify shape over several blocks: ragged lengths including a
    row with lengths < S (leading fully-masked query rows)."""
    rng = np.random.default_rng(12)
    q, stored, dense_kv = _blocks(rng, 3, 48, 8, 4, 16, 4, "float32", True)
    lengths = jnp.asarray([4, 30, 48], jnp.int32)
    walked = _assert_parity(q, stored, dense_kv, lengths, 16, 1e-5)
    k, v, ks, vs = stored
    one_block = np.asarray(flash_decode_attention(
        q, k, v, lengths, q.shape[-1] ** -0.5, k_scale=ks, v_scale=vs,
        block_t=48, interpret=True))
    np.testing.assert_allclose(walked, one_block, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# flash chunked prefill: the q-blocked grid (flash_attention machinery)
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("quantized", [False, True])
def test_chunk_q_blocking_matches_dense(quantized):
    """B=1 chunk windows wide enough to split over the q grid axis
    (block_q below S*g forces multiple q-tiles): every tile walks only
    its causal band's KV blocks and the assembled output is allclose to
    dense — first chunk, mid-prompt resume, ragged final, full window."""
    rng = np.random.default_rng(13)
    B, T, nh, nkv, D, S = 1, MAX_LEN, 8, 4, 16, 24
    q, stored, dense_kv = _blocks(rng, B, T, nh, nkv, D, S,
                                  "float32", quantized)
    k, v, ks, vs = stored
    scale = D ** -0.5
    for length in (S, 40, 61, MAX_LEN):
        lengths = jnp.asarray([length], jnp.int32)
        want = np.asarray(
            decode_attention(q, dense_kv[0], dense_kv[1], lengths, scale))
        got = np.asarray(flash_decode_attention(
            q, k, v, lengths, scale, k_scale=ks, v_scale=vs,
            block_t=16, block_q=16, interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        # q-blocked == single-tile (the pre-blocking layout)
        one = np.asarray(flash_decode_attention(
            q, k, v, lengths, scale, k_scale=ks, v_scale=vs,
            block_t=16, interpret=True))
        np.testing.assert_allclose(got, one, rtol=1e-6, atol=1e-6)


def test_chunk_q_blocking_paged():
    """The paged chunk shape (prefix-sharing resume attends over pages
    the chunk never wrote) with q-tiles narrower than the window."""
    rng = np.random.default_rng(14)
    B, nh, nkv, D, S = 1, 8, 4, 16, 16
    q, stored, dense_kv, tables = _paged_blocks(
        rng, B, 6, 8, nkv, D, S, nh, False)
    k, v, _, _ = stored
    scale = D ** -0.5
    for length in (S, 37, 48):
        lengths = jnp.asarray([length], jnp.int32)
        want = np.asarray(
            decode_attention(q, dense_kv[0], dense_kv[1], lengths, scale))
        got = np.asarray(flash_decode_attention(
            q, k, v, lengths, scale, block_tables=tables, block_q=16,
            interpret=True))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------- #
# engine-level wiring: attend_impl reaches all three jitted call sites
# --------------------------------------------------------------------------- #


def _engine(tiny_model_kwargs, impl, **kw):
    cfg = make_config(tiny_model_kwargs, tp=1, seq=MAX_LEN)
    return cfg, InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                                attend_impl=impl, **kw)


def _params(cfg, engine):
    p = jax.jit(lambda k: llama.init_params(k, cfg.model))(
        jax.random.PRNGKey(0))
    return engine.shard_params(p)


@pytest.mark.parametrize("heads", ["d8", "d64"])
@pytest.mark.parametrize("cache_dtype", [None, "int8"])
def test_engine_flash_decode_block_matches_dense(tiny_model_kwargs,
                                                 cache_dtype, heads):
    """The blocked decode dispatch (S=1 site) generates the same greedy
    tokens under both impls, fp32 and int8 caches; with two heads a row of
    the cache the kernel is handed the head-a-row view of it."""
    tiny_model_kwargs = with_heads(tiny_model_kwargs, heads)
    outs = {}
    for impl in ("dense", "flash"):
        cfg, eng = _engine(tiny_model_kwargs, impl, decode_block_len=4,
                           cache_dtype=cache_dtype)
        params = _params(cfg, eng)
        cache = eng.init_cache()
        kv, logits = eng.prefill(params, list(range(1, 9)))
        cache = eng.insert(cache, kv, 0, 8)
        toks = np.array([int(np.argmax(np.asarray(logits)[0])), 0], np.int32)
        keys = jnp.stack([jax.random.PRNGKey(7)] * 4)
        r = eng.decode_block(
            params, cache, toks, keys, np.full(2, -1, np.int32),
            np.array([8, 0], np.int32), np.zeros(2, np.float32),
            np.zeros(2, np.int32), np.ones(2, np.float32))
        cache, blk, counts = r.cache, r.tokens, r.counts
        outs[impl] = (np.asarray(blk), np.asarray(counts),
                      np.asarray(cache["lengths"]))
    for a, b in zip(outs["dense"], outs["flash"]):
        np.testing.assert_array_equal(a, b)
    assert outs["flash"][1].tolist() == [4, 0]  # free slot stayed inert


def test_engine_flash_verify_matches_dense(tiny_model_kwargs):
    """The speculative verify dispatch (S>1, B>1 site): same emitted
    tokens, counts, accepted-draft counts, and length pointers."""
    outs = {}
    for impl in ("dense", "flash"):
        cfg, eng = _engine(tiny_model_kwargs, impl, spec_len=3)
        params = _params(cfg, eng)
        cache = eng.init_cache()
        for slot in (0, 1):
            kv, logits = eng.prefill(params, list(range(1 + slot, 9 + slot)))
            cache = eng.insert(cache, kv, slot, 8)
        tokens = np.array([[3, 5, 7, 9], [4, 6, 8, 10]], np.int32)
        r = eng.verify(
            params, cache, tokens, jax.random.PRNGKey(3),
            np.full(2, -1, np.int32), np.full(2, 8, np.int32),
            np.zeros(2, np.float32), np.zeros(2, np.int32),
            np.ones(2, np.float32))
        cache, emitted = r.cache, r.tokens
        counts, accepted = r.counts, r.accepted
        outs[impl] = tuple(np.asarray(x) for x in
                           (emitted, counts, accepted, cache["lengths"]))
    for a, b in zip(outs["dense"], outs["flash"]):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("heads", ["d8", "d64"])
def test_engine_flash_chunked_prefill_matches_dense(tiny_model_kwargs, heads):
    """The chunked-prefill dispatch (B=1, S=chunk site): final-chunk logits
    agree across impls AND with the one-shot prefill oracle (ragged final
    chunk included: 20 tokens over width-8 chunks)."""
    tiny_model_kwargs = with_heads(tiny_model_kwargs, heads)
    prompt = [(5 * i + 2) % 199 + 1 for i in range(20)]
    logits = {}
    for impl in ("dense", "flash"):
        cfg, eng = _engine(tiny_model_kwargs, impl, prefill_chunk=8)
        params = _params(cfg, eng)
        cache, last = eng.prefill_chunked(params, eng.init_cache(),
                                          prompt, slot=1)
        assert int(np.asarray(cache["lengths"])[1]) == len(prompt)
        logits[impl] = np.asarray(last)[0]
        if impl == "dense":
            _, oneshot = eng.prefill(params, prompt)
    np.testing.assert_allclose(logits["flash"], logits["dense"],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(logits["dense"], np.asarray(oneshot)[0],
                               rtol=1e-4, atol=1e-4)


def test_engine_flash_matches_dense_tp2(tiny_model_kwargs):
    """On a tp=2 dryrun mesh the cache's kv-head axis is sharded, so each
    shard's kernel instance sees the LOCAL head count — greedy decode must
    still match dense exactly."""
    tokens = {}
    for impl in ("dense", "flash"):
        cfg = make_config(dict(tiny_model_kwargs, num_hidden_layers=2),
                          tp=2, seq=MAX_LEN)
        eng = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                              attend_impl=impl)
        params = _params(cfg, eng)
        cache = eng.init_cache()
        kv, logits = eng.prefill(params, list(range(1, 9)))
        cache = eng.insert(cache, kv, 0, 8)
        toks = np.array([int(np.argmax(np.asarray(logits)[0])), 0],
                        np.int32)
        got, key = [], jax.random.PRNGKey(1)
        for _ in range(4):
            key, sub = jax.random.split(key)
            cache, toks, _ = eng.decode_step(
                params, cache, toks, sub, np.zeros(2, np.float32),
                np.zeros(2, np.int32), np.ones(2, np.float32))
            toks = np.asarray(toks)
            got.append(int(toks[0]))
        tokens[impl] = got
    assert tokens["dense"] == tokens["flash"]


def test_attend_impl_validated(tiny_model_kwargs):
    """Bad impl strings fail loudly at engine build and config load."""
    cfg = make_config(tiny_model_kwargs, tp=1, seq=MAX_LEN)
    with pytest.raises(ValueError, match="attend_impl"):
        InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                        attend_impl="paged")
    raw = cfg.to_dict()
    raw["inference"]["attend_impl"] = "paged"
    from picotron_tpu.config import Config

    with pytest.raises(ValueError, match="attend_impl"):
        Config.from_dict(raw)
    # the attend helper itself must not silently fall through to dense
    q = jnp.zeros((1, 1, 2, 4))
    cache = {"k": jnp.zeros((1, 1, 8, 2, 4)),
             "v": jnp.zeros((1, 1, 8, 2, 4))}
    with pytest.raises(ValueError, match="attend impl"):
        kv_cache.attend(q, cache, jnp.ones(1, jnp.int32), 0.5, 0,
                        impl="Flash")


# --------------------------------------------------------------------------- #
# the plain decode step on the stacked, packed leaf (flash_decode_stacked)
# --------------------------------------------------------------------------- #

ST_LAYERS, ST_T, ST_ROWS, ST_BLOCK = 3, 32, 2, 8
# a free slot, one token, a block's edge on either side, the whole window
ST_LENGTHS = [0, 1, ST_BLOCK, ST_BLOCK + 1, ST_T]


def _stacked(rng, p, g):
    """(q [B, 1, heads, D], the K and V leaves [L, B, T, rows, 128]) in
    bfloat16: ``p`` kv heads of ``128 / p`` side by side in a row, ``g``
    query heads a kv head."""
    D, B = 128 // p, len(ST_LENGTHS)
    leaf = (ST_LAYERS, B, ST_T, ST_ROWS, 128)
    q = rng.normal(size=(B, 1, ST_ROWS * p * g, D))
    k, v = rng.normal(size=leaf), rng.normal(size=leaf)
    return tuple(jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))


def _stacked_jit(scale):
    """One compile a (shape, scale): the layer index is traced."""
    return jax.jit(lambda q, k, v, n, layer: flash_decode_stacked(
        q, k, v, n, scale, layer, block_t=ST_BLOCK, interpret=True))


# SmolLM's rows (two heads of 64, MHA), Mistral's (a head of 128, four query
# heads each), the two mixed, and Granite's: Mistral's rows under a scale
# that is not ``D ** -0.5`` (a power of two: folded into q)
@pytest.mark.parametrize("p,g,scale", [
    (2, 1, None), (1, 4, None), (1, 1, None), (2, 4, None),
    (1, 4, 1.0 / 128)])
def test_stacked_kernel_matches_dense(p, g, scale):
    """The new entry point against ``decode_attention`` on the layer's
    block: packed and plain rows, MHA and GQA, the first and the last layer
    of the leaf through a TRACED index, lengths from a free slot to the
    whole window over a walk of four blocks."""
    q, k, v = _stacked(np.random.default_rng(10 * p + g), p, g)
    lengths = jnp.asarray(ST_LENGTHS, jnp.int32)
    scale = scale or q.shape[-1] ** -0.5
    kernel = _stacked_jit(scale)
    dense = jax.jit(lambda q, k, v, n, layer: decode_attention(
        q, k[layer], v[layer], n, scale))
    for layer in (0, ST_LAYERS - 1):
        got = kernel(q, k, v, lengths, jnp.int32(layer))
        want = dense(q, k, v, lengths, jnp.int32(layer))
        assert got.shape == want.shape and got.dtype == want.dtype
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        np.testing.assert_allclose(got[1:], want[1:], rtol=2e-2, atol=2e-2)
        # the free slot: zeros, where dense emits an average nothing reads
        assert np.all(got[0] == 0.0) and np.any(want[0] != 0.0)
    # the layers differ, so a kernel that read the wrong one would show
    assert np.abs(got[1:] - np.asarray(
        kernel(q, k, v, lengths, jnp.int32(0)), np.float32)[1:]).max() > 0.1


def test_stacked_kernel_ignores_stale_rows_and_other_layers():
    """Rows at and past a slot's length, and every other layer of the
    leaf, may hold anything: the output does not move."""
    q, k, v = _stacked(np.random.default_rng(21), 2, 1)
    lengths = jnp.asarray([0, 3, 8, 9, 20], jnp.int32)
    run = lambda k, v: np.asarray(_stacked_jit(0.125)(
        q, k, v, lengths, jnp.int32(1)), np.float32)
    stale = jnp.arange(ST_T)[None, :, None, None] >= lengths[:, None, None,
                                                            None]
    junk = lambda x: jnp.where(stale[None], 1e4, x).at[0].set(-1e4).at[
        2].set(1e4).astype(x.dtype)
    np.testing.assert_array_equal(run(k, v), run(junk(k), junk(v)))


# the dense form's walk inside the kernel (PR 63), over a strip of six blocks
# of 32 tokens (copied in four pieces of 8 tokens where a token is two rows
# or more, in two where it is one): the longest slot first and last, free
# slots between live ones, and about a block's edge one token, one short of a
# block, a block, one over, a last block that ends in its first, second and
# third piece, three blocks, and a length past the strip (clamped)
WALK_T, WALK_BLOCK = 192, 32
WALK_LENGTHS = [192, 0, 1, 31, 32, 0, 33, 40, 41, 83, 96, 200, 0, 192]
# (cache rows, kv heads a row, query heads a kv head, K head, V head, sink)
WALK_CASES = {
    "p1_g1": (2, 1, 1, 128, 128, False), "p2_g1": (2, 2, 1, 64, 64, False),
    "p1_g4": (2, 1, 4, 128, 128, False), "p2_g4": (2, 2, 4, 64, 64, False),
    # SDAR's call at toy size: a block of 4 positions beside the 8 query
    # heads of a kv head, 128 query rows a slot against 4 cache rows
    "sdar_128_rows": (4, 1, 32, 128, 128, False),
    "sink": (2, 1, 4, 128, 128, True),
    # MiMo's full layers: a row's four heads merged, K wider than V
    "narrow_v": (1, 4, 4, 192, 128, False),
    "narrow_v_sink": (1, 4, 4, 192, 128, True),
}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_dense_form_walks_live_blocks_and_keeps_the_parents_bits(case):
    """The dense form against the dense answer on the layer's block, and bit
    for bit against the kernel it replaced (``parent_dense_decode``: a grid
    step a block of the strip, live or not), whose blocks, order and
    arithmetic it keeps: every length of ``WALK_LENGTHS`` in one call, so a
    slot's first block is asked for by the slot before it, live or free."""
    from parent_dense_decode import flash_decode_stacked as parent
    from picotron_tpu.models import afmoe

    rows, p, g, D, Dv, with_sink = WALK_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    B, nh = len(WALK_LENGTHS), rows * p * g
    bf16 = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    q = bf16(B, 1, nh, D)
    k, v = bf16(2, B, WALK_T, rows, p * D), bf16(2, B, WALK_T, rows, p * Dv)
    sink = jnp.asarray(2.0 + rng.normal(size=nh), jnp.float32) \
        if with_sink else None
    lengths = jnp.asarray(WALK_LENGTHS, jnp.int32)
    got = jax.jit(lambda *a: flash_decode_stacked(
        *a, D ** -0.5, 1, block_t=WALK_BLOCK, interpret=True,
        sink=sink))(q, k, v, lengths)
    assert got.shape == (B, 1, nh, Dv) and got.dtype == jnp.bfloat16
    k4 = k[1].reshape(B, WALK_T, rows * p, D)
    v4 = v[1].reshape(B, WALK_T, rows * p, Dv)
    if sink is None:
        pad = jnp.pad(v4, ((0, 0),) * 3 + ((0, D - Dv),))
        want = decode_attention(q, k4, pad, lengths, D ** -0.5)[..., :Dv]
    else:
        seen = jnp.arange(WALK_T)[None, :] < lengths[:, None]
        want = afmoe.masked_attention(q, k4, v4, seen[:, None],
                                      D ** -0.5, sink)
    live = np.asarray(lengths) > 0
    got32, want32 = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got32[live], want32[live], rtol=2e-2,
                               atol=2e-2)
    assert np.all(got32[~live] == 0.0)
    np.testing.assert_array_equal(got32, np.asarray(parent(
        q, k, v, lengths, D ** -0.5, 1, block_t=WALK_BLOCK, sink=sink),
        np.float32))


# the sliding layers' rings of the Trinity cell, as
# tests/test_chip_compile.py::_decode_ring has them
RING_LEAF, RING_WINDOW, RING_HEADS = (7, 16, 4608, 8, 128), 4096, 48
RING_LOWERED = os.path.join(os.path.dirname(__file__), "data",
                            "ring_decode_trinity.lowered.txt")


def _ring_lowered(kernel):
    """The lowered text of ``kernel`` in its ring form at the Trinity
    cell's shape, interpret mode, source locations stripped."""
    BF16, I32 = jnp.bfloat16, jnp.int32
    S = jax.ShapeDtypeStruct
    text = jax.jit(lambda q, k, v, n, layer: kernel(
        q, k, v, n, 128 ** -0.5, layer, window=RING_WINDOW,
        interpret=True)).lower(
            S((RING_LEAF[1], 1, RING_HEADS, 128), BF16), S(RING_LEAF, BF16),
            S(RING_LEAF, BF16), S((RING_LEAF[1],), I32), S((), I32)).as_text()
    return re.sub(r"\s*loc\(.*?\)$|^#loc.*\n", "", text, flags=re.M)


def test_ring_form_lowers_to_the_program_it_was():
    """PR 63 gave the dense form a kernel and a call of its own; the ring
    form (Trinity's and MiMo's sliding layers) keeps the program it had,
    operation for operation: its lowered text equals the copy saved from
    the parent commit (``tests/data``, written by ``_ring_lowered`` over the
    parent's module)."""
    with open(RING_LOWERED) as f:
        assert _ring_lowered(flash_decode_stacked) == f.read()


# the dense form as two cells call it: a decode step of Mistral's rows (a
# head of 128 a row, four query heads each), and SDAR's denoise forward, a
# block of 4 rows beside the 8 query heads of each of 4 kv heads
DENSE_FORMS = {"S1_mistral": ((16, 8, 4096, 8, 128), 32),
               "Sblock_sdar": ((12, 32, 12288, 4, 128), 128)}


def _dense_lowered(kernel, form):
    """The lowered text of ``kernel`` in its dense form at a cell's shape,
    interpret mode, source locations stripped."""
    leaf, heads = DENSE_FORMS[form]
    S = jax.ShapeDtypeStruct
    text = jax.jit(lambda q, k, v, n, layer: kernel(
        q, k, v, n, 128 ** -0.5, layer, interpret=True)).lower(
            S((leaf[1], 1, heads, 128), jnp.bfloat16), S(leaf, jnp.bfloat16),
            S(leaf, jnp.bfloat16), S((leaf[1],), jnp.int32),
            S((), jnp.int32)).as_text()
    return re.sub(r"\s*loc\(.*?\)$|^#loc.*\n", "", text, flags=re.M)


@pytest.mark.parametrize("form", sorted(DENSE_FORMS))
def test_dense_form_without_early_lowers_to_the_program_it_was(form):
    """PR 66 gave the dense form a second limit a slot (``early``: two
    blocks of fresh rows in one call). A call without it, the ``S == 1``
    step's and the ``S == block`` forward's, keeps the program it had,
    operation for operation: its lowered text equals the copy saved from
    the parent commit (``tests/data``, written by ``_dense_lowered`` over
    the parent's module, gzipped: 340 KB of text a form)."""
    import gzip

    path = os.path.join(os.path.dirname(__file__), "data",
                        f"dense_decode_{form}.lowered.txt.gz")
    with gzip.open(path, "rt") as f:
        assert _dense_lowered(flash_decode_stacked, form) == f.read()


# two blocks of 4 fresh rows a slot against K blocks of 16 tokens, by where
# the two limits (``lengths - 4`` and ``lengths``, whole blocks both) lie:
# in one K block, the first or a later one; in two (the first half's limit
# is a K block's end, so of the walk's last block it sees nothing); an empty
# prefix; the window's end, past which the rows dropped (the kernel clamps
# its walk)
EARLY_T, EARLY_BLOCK_T = 64, 16
EARLY_LENGTHS = {"one_block": [12, 16, 28, 48], "two_blocks": [20, 36, 52, 20],
                 "empty_prefix": [8, 8, 8, 8], "window_end": [64, 68, 64, 68]}


@pytest.mark.parametrize("heads_a_row", [1, 2])
@pytest.mark.parametrize("case", sorted(EARLY_LENGTHS))
def test_two_blocks_ride_one_call_with_a_limit_each(case, heads_a_row,
                                                     monkeypatch):
    """``attend(impl="flash", block=4)`` on ``2 x block`` rows a slot: the
    stacked kernel's ``early`` form in interpret mode (the first block's
    query heads stop 4 keys before ``lengths``) against
    ``decode_attention(..., block=4)`` on the same rows, K/V a head a row
    and two heads packed to a row."""
    import picotron_tpu.ops.pallas.decode_attention as da

    D, rows = 128 // heads_a_row, 2
    monkeypatch.setattr(da, "_STACKED_KV_BLOCK",
                        EARLY_BLOCK_T * rows * 128 * 2)
    rng = np.random.default_rng(len(case) + heads_a_row)
    B, nh = 4, rows * heads_a_row * 4
    bf = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    q, k, v = bf(B, 8, nh, D), bf(2, B, EARLY_T, rows, 128), \
        bf(2, B, EARLY_T, rows, 128)
    cache = {"k": k, "v": v}
    assert kv_cache.plain_decode(q, cache, 4)
    lengths = jnp.asarray(EARLY_LENGTHS[case], jnp.int32)
    got = kv_cache.attend(q, cache, lengths, 0.25, 1, impl="flash", block=4)
    k4, v4 = (x[1].reshape(B, EARLY_T, rows * heads_a_row, D)
              for x in (k, v))
    want = decode_attention(q, k4, v4, lengths, 0.25, 4)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    # the halves differ: the first saw 4 keys fewer
    whole = kv_cache.attend(q[:, 4:], cache, lengths, 0.25, 1, impl="flash",
                            block=4)
    np.testing.assert_allclose(got[:, 4:], np.asarray(whole, np.float32),
                               rtol=2e-2, atol=2e-2)
    early = kv_cache.attend(q[:, :4], cache, lengths - 4, 0.25, 1,
                            impl="flash", block=4)
    np.testing.assert_allclose(got[:, :4], np.asarray(early, np.float32),
                               rtol=2e-2, atol=2e-2)


def _routes(monkeypatch, on_tpu):
    """Stand-ins for the three places ``attend`` can send a call, each
    returning its own name, with ``on_tpu`` as given."""
    import picotron_tpu.ops.pallas.decode_attention as da
    from picotron_tpu.inference import paged_kv

    monkeypatch.setattr(da, "flash_decode_stacked",
                        lambda *a, **kw: "stacked")
    monkeypatch.setattr(da, "flash_decode_attention",
                        lambda *a, **kw: "sliced")
    monkeypatch.setattr(kv_cache, "decode_attention", lambda *a: "dense")
    monkeypatch.setattr(paged_kv, "attend", lambda *a: "paged " + a[-1])
    monkeypatch.setattr(kv_cache, "on_tpu", lambda: on_tpu)


def _plain_call():
    q, k, v = _stacked(np.random.default_rng(7), 2, 1)
    return q, {"k": k, "v": v}, jnp.asarray(ST_LENGTHS, jnp.int32)


@pytest.mark.parametrize("impl,on_tpu,route", [
    ("auto", False, "dense"), ("auto", True, "stacked"),
    ("dense", True, "dense"), ("flash", False, "stacked"),
    ("flash", True, "stacked")])
def test_attend_routes_the_plain_decode_shape(impl, on_tpu, route,
                                              monkeypatch):
    """``auto``: dense off a TPU (no tier-1 test that serves on the CPU
    starts interpreting Pallas), the stacked kernel on one; ``flash`` takes
    the same entry point wherever it runs; ``dense`` is dense."""
    q, cache, lengths = _plain_call()
    assert kv_cache.plain_decode(q, cache)
    _routes(monkeypatch, on_tpu)
    assert kv_cache.attend(q, cache, lengths, 0.125, 1, impl=impl) == route


def _excluded(name, q, cache, lengths):
    """The plain call made into one of the shapes ``auto`` leaves to
    dense."""
    k = cache["k"]
    if name == "S>1":
        return jnp.concatenate([q, q], axis=1), cache, lengths + 1
    if name == "slot":
        return q[:1], {**cache, "slot": jnp.int32(1)}, lengths[1:2]
    if name == "gate":
        return q, {**cache, "gate": jnp.bool_(True)}, lengths
    if name == "draft_valid":
        return q, {**cache, "draft_valid": lengths}, lengths
    if name == "int8":
        scale = jnp.ones(k.shape[:3] + (k.shape[3] * 2,), jnp.float32)
        return q, {"k": k.astype(jnp.int8), "v": k.astype(jnp.int8),
                   "k_scale": scale, "v_scale": scale}, lengths
    if name == "float32":
        return q.astype(jnp.float32), {
            n: x.astype(jnp.float32) for n, x in cache.items()}, lengths
    assert name == "paged"
    pool = k[0].reshape(-1, 8, ST_ROWS * 2, 64)  # pages of 8 rows
    tables = jnp.arange(pool.shape[0], dtype=jnp.int32).reshape(
        k.shape[1], -1)
    return q, {"k": pool[None], "v": pool[None], "block_tables": tables}, \
        lengths


@pytest.mark.parametrize("shape", ["S>1", "slot", "gate", "draft_valid",
                                   "int8", "float32", "paged"])
def test_auto_leaves_every_other_shape_to_dense(shape, monkeypatch):
    """Prefill chunks and verify (S > 1), one slot's block (``slot``,
    ``gate``), a ragged verify (``draft_valid``), int8 and float32 leaves
    and the paged layout are not the plain decode shape: on a TPU too,
    ``auto`` goes where ``dense`` goes, and ``flash`` to the kernel that
    takes the sliced layer (paged: the page walk) as before."""
    q, cache, lengths = _excluded(shape, *_plain_call())
    assert not kv_cache.plain_decode(q, cache)
    _routes(monkeypatch, on_tpu=True)
    dense = "paged dense" if shape == "paged" else "dense"
    flash = "paged flash" if shape == "paged" else "sliced"
    attend = lambda impl: kv_cache.attend(q, cache, lengths, 0.125, 0,
                                          impl=impl)
    assert [attend("auto"), attend("dense"), attend("flash")] \
        == [dense, dense, flash]


def test_engine_auto_is_the_default_and_dense_off_tpu(tiny_model_kwargs):
    """The shipped default is ``auto``; an engine built on it off a TPU
    has nothing to fall back from."""
    cfg, eng = _engine(tiny_model_kwargs, None)
    assert cfg.inference.attend_impl == eng.attend_impl == "auto"
    assert not eng._flash_fallback(RuntimeError("x"))
    assert eng.attend_impl == "auto"
