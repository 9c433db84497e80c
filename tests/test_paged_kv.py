"""Paged KV cache suite (ISSUE 7; inference/paged_kv.py).

Three layers of pinning:

- **allocator invariants** (pure host): alloc/free round-trips never
  double-free, refcounts never go negative (both raise instead), radix
  eviction frees exactly the refcount-1 leaves LRU-first, COW planning
  swaps references without leaking;
- **byte equivalence** (device): the paged scatter/gather write and
  attend paths produce byte-identical K/V rows and identical attention
  outputs to the contiguous layout, fp32 and int8, dense and flash;
- **generation equivalence** (engine + batcher): with
  ``inference.kv_layout: "paged"``, greedy generations through blocked
  decode, speculative verify (incl. rollback), and chunked prefill are
  IDENTICAL to the contiguous layout — bf16 and int8 caches, dense and
  flash attends, tp=1 and tp=2 — and prefix sharing/COW are invisible in
  the output: forked requests generate exactly what independent requests
  would, while the shared pages' bytes never change.

Plus the capacity story the subsystem exists for: a shared-prefix
workload's prefill work and live pages scale with UNIQUE tokens, not
requests x prompt length, and out-of-pages admission sheds at the door
instead of corrupting a live slot (the serve front end's 429 carries a
pool-pressure Retry-After).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_config
from picotron_tpu.inference import (
    ContinuousBatcher,
    InferenceEngine,
    Request,
    paged_kv,
)
from picotron_tpu.inference.paged_kv import (
    NULL_PAGE,
    PagedKV,
    PagePool,
    PagePoolExhausted,
    RadixCache,
)
from picotron_tpu.models import llama

MAX_LEN = 64
PAGE = 8

_TINY = dict(
    num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
    hidden_size=64, intermediate_size=128, vocab_size=256,
    max_position_embeddings=MAX_LEN, rope_theta=10000.0, dtype="float32",
    attention_impl="sdpa")


# --------------------------------------------------------------------------- #
# allocator invariants (pure host)
# --------------------------------------------------------------------------- #


def test_pool_alloc_free_roundtrip_and_double_free():
    pool = PagePool(5)  # 4 usable + NULL
    got = [pool.alloc() for _ in range(4)]
    assert sorted(got) == [1, 2, 3, 4] and NULL_PAGE not in got
    assert pool.alloc() is None  # dry pool is a None, not corruption
    assert pool.free_count == 0 and pool.live_count == 4
    for pid in got:
        assert pool.unref(pid)  # refcount 1 -> 0 frees
    assert pool.free_count == 4
    with pytest.raises(ValueError, match="double free"):
        pool.unref(got[0])  # refcount already 0
    with pytest.raises(ValueError, match="resurrect"):
        pool.ref(got[0])  # a freed page cannot be re-shared
    # refcounted sharing: two holders, page survives the first drop
    pid = pool.alloc()
    pool.ref(pid)
    assert not pool.unref(pid)
    assert pool.unref(pid)
    with pytest.raises(ValueError):
        pool.ref(NULL_PAGE)


def test_radix_match_insert_evict():
    pool = PagePool(16)
    radix = RadixCache(PAGE, pool)
    # "prefill" a 19-token prompt: two full pages + a 3-row partial tail
    prompt = list(range(100, 119))
    pages = [pool.alloc() for _ in range(3)]
    assert radix.insert(prompt, lambda i: pages[i]) == 3
    assert [pool.refs[p] for p in pages] == [2, 2, 2]  # slot + cache
    # exact full-prefix + partial-tail match
    got, matched = radix.match(prompt + [7, 8])
    assert matched == 19 and got == pages
    # mid-page fork: 11 tokens shared means page0 full + 3 rows of page1
    got, matched = radix.match(prompt[:11] + [9, 9, 9])
    assert matched == 11 and got == pages[:2]
    # no overlap at all
    assert radix.match([1, 2, 3]) == ([], 0)
    # the slot releases its references; pages are now cache-only (refs 1)
    for p in pages:
        pool.unref(p)
    assert radix.evictable_count() == 3  # the refcount-1 chain cascades
    # a second prompt sharing page0 keeps it alive through eviction
    pool.ref(pages[0])
    assert radix.evictable_count() == 2
    assert radix.evict_one() and radix.evict_one()  # tail first (LRU leaf)
    assert pool.refs[pages[1]] == 0 and pool.refs[pages[2]] == 0
    assert not radix.evict_one()  # page0 is shared: nothing evictable
    assert pool.refs[pages[0]] == 2
    assert radix.evictions == 2


def test_manager_cow_planning_and_free_slot():
    mgr = PagedKV(slots=2, page_len=PAGE, max_pages=4, num_pages=16)
    # slot 0 grows into two fresh pages — no COW on exclusive pages
    assert mgr.ensure_writable(0, 0, 12) == []
    held = [int(p) for p in mgr.tables[0, :2]]
    assert all(p != NULL_PAGE for p in held)
    assert mgr.ensure_writable(0, 8, 12) == []  # idempotent
    # share slot 0's first page into slot 1 (what a prefix hit does)
    mgr.pool.ref(held[0])
    mgr.tables[1, 0] = held[0]
    # slot 1's first write into the shared page must plan exactly one COW
    cows = mgr.ensure_writable(1, 4, 9)
    assert len(cows) == 1 and cows[0][0] == held[0]
    assert mgr.tables[1, 0] == cows[0][1] != held[0]
    assert mgr.pool.refs[held[0]] == 1  # slot 1 dropped its reference
    mgr.set_len(0, 12)
    mgr.free_slot(0)
    assert mgr.pool.refs[held[0]] == 0 and mgr.pool.refs[held[1]] == 0
    assert np.all(mgr.tables[0] == NULL_PAGE) and mgr.host_len[0] == 0
    mgr.free_slot(1)
    assert mgr.pool.free_count == mgr.pool.usable_pages


def test_match_prefix_idempotent_under_retry():
    """The batcher retries a faulted prefill dispatch, which re-runs the
    whole admission (match_prefix included) on the same slot. The re-match
    must release the failed attempt's holdings first — or shared pages
    double-ref (never evictable, never freed) and stranded COW copies
    leak outright."""
    mgr = PagedKV(slots=1, page_len=PAGE, max_pages=4, num_pages=16)
    prompt = list(range(100, 118))  # 2 full pages + 2-row tail
    # seed the radix cache as a completed request would
    mgr.ensure_writable(0, 0, len(prompt))
    cached_pages = [int(p) for p in mgr.tables[0] if p != NULL_PAGE]
    mgr.set_len(0, len(prompt))
    mgr.register_prompt(0, prompt)
    mgr.free_slot(0)
    live0 = mgr.pool.live_count
    # attempt 1 matches, COWs the fork page, then "fails"; attempt 2
    # re-matches the same slot
    assert mgr.match_prefix(0, prompt + [7]) == 18
    mgr.ensure_writable(0, 18, 19)  # the suffix COW a real attempt plans
    assert mgr.match_prefix(0, prompt + [7]) == 18  # the retry
    mgr.free_slot(0)  # the admission ultimately fails -> slot released
    # nothing leaked: pool back to the radix-only footprint, every cached
    # page at exactly the cache's one reference (still evictable)
    assert mgr.pool.live_count == live0
    assert all(mgr.pool.refs[p] == 1 for p in cached_pages)
    assert mgr.radix.evictable_count() == live0


def test_manager_exhaustion_raises_not_corrupts():
    mgr = PagedKV(slots=1, page_len=PAGE, max_pages=4, num_pages=3)
    mgr.ensure_writable(0, 0, 16)  # both usable pages
    before = mgr.tables[0].copy()
    with pytest.raises(PagePoolExhausted):
        mgr.ensure_writable(0, 16, 24)
    np.testing.assert_array_equal(mgr.tables[0], before)  # untouched


# --------------------------------------------------------------------------- #
# byte equivalence (device ops)
# --------------------------------------------------------------------------- #


def _cfg(tp=1, **inf):
    cfg = make_config(dict(_TINY), tp=tp, seq=32)
    for k, v in inf.items():
        setattr(cfg.inference, k, v)
    return cfg


def _engines(tp=1, slots=3, **kw):
    """(contiguous engine, paged engine) over one tiny config. The
    contiguous one is the reference: it keeps no prefix store
    (tests/test_prefix_store.py) and prefills every prompt whole."""
    cfg = _cfg(tp=tp)
    ec = InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN,
                         kv_layout="contiguous", kv_store_pages=-1, **kw)
    ep = InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN,
                         kv_layout="paged", kv_page_len=PAGE, **kw)
    params = ec.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    return cfg, ec, ep, params


@pytest.mark.parametrize("storage", ["bf16", "int8", "hot_bf16"])
@pytest.mark.parametrize("shape", ["decode", "chunk", "verify",
                                   "ragged_verify"])
def test_layer_indexed_seam_matches_per_layer(shape, storage):
    """The paged ``cache_write`` / ``attend`` addressed
    ``[layer, page, row]`` into the STACKED pool leaves (what the engine's
    layer scan carries) produce bit for bit the pool bytes and the
    attention output of the same functions on that layer's slice alone —
    the three write shapes, the ragged ``draft_valid`` mask, int8 and the
    hot_bf16 dual pool — and touch no other layer."""
    rng = np.random.default_rng(11)
    L, B, P, H, D, layer, maxp = 3, 3, 13, 2, 8, 2, 4
    pool = lambda: jnp.asarray(rng.normal(size=(L, P, PAGE, H, D)),
                               jnp.bfloat16)
    cache = {"k": pool(), "v": pool()}
    if storage != "bf16":
        (qk, ks), (qv, vs) = (paged_kv.kv_cache.quantize_kv(cache[n])
                              for n in "kv")
        cache = ({"k": qk, "v": qv} if storage == "int8"
                 else {**cache, "k_q": qk, "v_q": qv})
        cache.update(k_scale=ks, v_scale=vs)
    meta = {"block_tables": jnp.asarray(
        1 + rng.permutation(P - 1)[:B * maxp].reshape(B, maxp), jnp.int32)}
    if storage == "hot_bf16":
        meta["page_quant"] = jnp.asarray(rng.integers(0, 2, P), jnp.int32)
    if shape == "decode":
        b, s, pos = B, 1, [6, 19, 0]
    elif shape == "chunk":
        b, s, pos = 1, 12, [5]  # crosses a page boundary
        meta["block_tables"] = meta["block_tables"][1:2]
    else:
        b, s, pos = B, 4, [6, 30, 2]  # slot 1 runs off its window's end
        if shape == "ragged_verify":
            meta["draft_valid"] = jnp.asarray([4, 1, 2], jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(b, s, H, D)), jnp.bfloat16)
                    for _ in "kv")
    q = jnp.asarray(rng.normal(size=(b, s, 2 * H, D)), jnp.bfloat16)

    # the per-layer twin: that layer's slice as a pool of its own
    got = paged_kv.cache_write({**cache, **meta}, k_new, v_new, pos, layer)
    want = paged_kv.cache_write(
        {**{n: a[layer:layer + 1] for n, a in cache.items()}, **meta},
        k_new, v_new, pos, 0)
    for n, a in cache.items():
        new = np.asarray(got[n].astype(jnp.float32))
        np.testing.assert_array_equal(
            new[layer], np.asarray(want[n].astype(jnp.float32))[0])
        other = [i for i in range(L) if i != layer]
        np.testing.assert_array_equal(
            new[other], np.asarray(a.astype(jnp.float32))[other])
    for impl in ("dense", "flash"):
        out = paged_kv.attend(q, got, pos + s, 0.3, layer, impl)
        ref = paged_kv.attend(q, want, pos + s, 0.3, 0, impl)
        np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                      np.asarray(ref.astype(jnp.float32)))


@pytest.mark.parametrize("cache_dtype", [None, "int8"])
def test_insert_bytes_match_contiguous(cache_dtype):
    """A one-shot prefill parked through page indirection holds byte-
    identical K/V (and scale) rows to the contiguous insert."""
    cfg, ec, ep, params = _engines(cache_dtype=cache_dtype)
    prompt = list(range(1, 20))  # 2 full pages + a 3-row tail
    kv, _ = ec.prefill(params, prompt)
    cc = ec.insert(ec.init_cache(), kv, 1, len(prompt))
    pc = ep.insert(ep.init_cache(), kv, 1, len(prompt))
    names = ["k", "v"] + (["k_scale", "v_scale"] if cache_dtype else [])
    for name in names:
        want = np.asarray(cc[name])[:, 1, :len(prompt)]
        got = paged_kv.slot_rows(pc, ep.paged.tables, 1, len(prompt), name)
        np.testing.assert_array_equal(got, want)
    assert int(np.asarray(pc["lengths"])[1]) == len(prompt)


def test_cow_copy_page_is_byte_exact():
    cfg, ec, ep, params = _engines(cache_dtype="int8")
    kv, _ = ep.prefill(params, list(range(1, 17)))
    cache = ep.insert(ep.init_cache(), kv, 0, 16)
    src = int(ep.paged.tables[0, 1])
    dst = ep.paged.pool.alloc()
    before = {n: np.asarray(cache[n])[:, src].copy()
              for n in ("k", "v", "k_scale", "v_scale")}
    cache = ep._copy_page_jit(cache, src, dst)
    for n, want in before.items():
        got = np.asarray(cache[n])
        np.testing.assert_array_equal(got[:, dst], want)  # copy exact
        np.testing.assert_array_equal(got[:, src], want)  # parent intact


# --------------------------------------------------------------------------- #
# generation equivalence (engine + batcher)
# --------------------------------------------------------------------------- #


_PROMPTS = [list(range(1, 11)), [11, 12, 13],
            [1, 2, 3, 4, 5, 6, 7, 8, 21, 22]]  # 8-token shared prefix


def _generate(engine, params, seed=0, prompts=_PROMPTS, max_new=10,
              **req_kw):
    b = ContinuousBatcher(engine, params, seed=seed)
    res = b.run([Request(uid=f"r{i}", prompt=list(p),
                         max_new_tokens=max_new, **req_kw)
                 for i, p in enumerate(prompts)])
    return {u: r.tokens for u, r in res.items()}, b


@pytest.mark.parametrize("cache_dtype,attend_impl", [
    (None, "dense"), (None, "flash"),
    ("int8", "dense"), ("int8", "flash")])
def test_blocked_decode_generations_match_contiguous(cache_dtype,
                                                     attend_impl):
    """The core pin: paged == contiguous token streams through prefill +
    blocked decode, across cache dtypes and attend kernels, on a batch
    with a shared prefix (so sharing + COW are exercised AND invisible)."""
    cfg, ec, ep, params = _engines(cache_dtype=cache_dtype,
                                   attend_impl=attend_impl,
                                   decode_block_len=4)
    want, _ = _generate(ec, params)
    got, bp = _generate(ep, params)
    assert got == want
    s = bp.stats()
    assert s["prefix_hits"] >= 1 and s["cow_copies"] >= 1


def test_bf16_generations_match_contiguous():
    cfg = make_config(dict(_TINY), tp=1, seq=32, dtype="bfloat16")
    ec = InferenceEngine(cfg, slots=3, max_seq_len=MAX_LEN,
                         kv_layout="contiguous", decode_block_len=4)
    ep = InferenceEngine(cfg, slots=3, max_seq_len=MAX_LEN,
                         kv_layout="paged", kv_page_len=PAGE,
                         decode_block_len=4)
    params = ec.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    want, _ = _generate(ec, params)
    got, _ = _generate(ep, params)
    assert got == want


def test_speculative_verify_generations_match_contiguous():
    """Draft-verify with rollback: the optimistic writes land in pages,
    rejected rows strand beyond the length pointer — and the emitted
    streams still equal the contiguous layout's exactly."""
    cfg, ec, ep, params = _engines(spec_len=3)
    want, bc = _generate(ec, params, max_new=12)
    got, bp = _generate(ep, params, max_new=12)
    assert got == want
    assert bp.draft_proposed > 0  # speculation actually ran


def test_chunked_prefill_generations_match_contiguous():
    """Long prompts (over prefill_chunk) take the chunked path on both
    layouts; the ragged final chunk and the page-scatter writes agree."""
    prompts = [list(range(1, 30)), list(range(1, 30)) + [40, 41]]
    cfg, ec, ep, params = _engines(prefill_chunk=8)
    want, _ = _generate(ec, params, prompts=prompts, max_new=8)
    got, _ = _generate(ep, params, prompts=prompts, max_new=8)
    assert got == want


def test_tp2_generations_match_contiguous(tiny_model_kwargs):
    """tp=2: the pool's kv-head axis is sharded; block tables and the
    allocator are replicated host state — generations must not notice."""
    cfg = make_config(dict(_TINY), tp=2, seq=32)
    ec = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                         kv_layout="contiguous")
    ep = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                         kv_layout="paged", kv_page_len=PAGE)
    params = ec.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    want, _ = _generate(ec, params, prompts=_PROMPTS[:2], max_new=6)
    got, _ = _generate(ep, params, prompts=_PROMPTS[:2], max_new=6)
    assert got == want


def test_eos_and_timeout_slot_recycling_paged():
    """Retired slots (EOS mid-stream) release refcounted pages and the
    recycled slot serves the queue — more requests than slots."""
    cfg, ec, ep, params = _engines(slots=2)
    prompts = [[i + 1, i + 2, i + 3] for i in range(5)]
    want, _ = _generate(ec, params, prompts=prompts, max_new=6, eos_id=5)
    got, bp = _generate(ep, params, prompts=prompts, max_new=6, eos_id=5)
    assert got == want
    assert bp.counters["completed"] == 5
    # every slot's pages released; only radix-cached prefix pages remain
    p = ep.paged
    assert np.all(p.tables == NULL_PAGE)
    assert p.pool.live_count == p.radix.evictable_count()


# --------------------------------------------------------------------------- #
# prefix sharing: capacity scales with unique tokens; COW is invisible
# --------------------------------------------------------------------------- #


def test_shared_prefix_scales_with_unique_tokens():
    """N requests behind one long system prompt: prefill dispatches and
    live pages track the UNIQUE tokens, not N x prompt length."""
    system = list(range(1, 41))  # 5 full pages
    prompts = [system + [50 + i] for i in range(4)]
    cfg, ec, ep, params = _engines(slots=4, prefill_chunk=8)
    want, bc = _generate(ec, params, prompts=prompts, max_new=4)
    got, bp = _generate(ep, params, prompts=prompts, max_new=4)
    assert got == want
    # contiguous prefills the full prompt 4 times (5+1 chunks each);
    # paged prefills it once and then only suffixes
    assert bc.prefill_dispatches == 4 * 6
    assert bp.prefill_dispatches < bc.prefill_dispatches / 2
    s = bp.stats()
    assert s["prefix_hits"] == 3
    # 3 followers x 40 cached tokens = 120 of 164 prompt tokens served
    # from the cache
    assert s["prefix_cached_tokens"] == 3 * len(system)
    assert s["prefix_hit_rate"] > 0.7
    # capacity: unique tokens ~ 41 + 3 extra tails, nowhere near 4x44
    unique_pages_bound = ep.paged.pages_for(len(system) + 8) + 2 * 4
    assert s["kv_pages_live"] <= unique_pages_bound
    assert s["kv_pages_live"] < 4 * ep.paged.pages_for(len(prompts[0]))


def test_cow_forked_generations_equal_independent_and_preserve_bytes():
    """The COW acceptance pin: requests forking from a shared prefix
    generate exactly what fully-independent requests would, and the
    radix-cached pages' bytes are unchanged after all of them finish."""
    base = list(range(1, 20))  # forks mid-page (19 = 2 pages + 3 rows)
    forks = [base + [30], base + [31], base[:11] + [32]]
    cfg, ec, ep, params = _engines(slots=1)  # serialize: maximal reuse
    want, _ = _generate(ec, params, prompts=forks, max_new=6)

    b = ContinuousBatcher(ep, params, seed=0)
    res = b.run([Request(uid="r0", prompt=forks[0], max_new_tokens=6)])
    # snapshot every radix-held page AFTER the seeding request finished
    frozen = {}
    for node in ep.paged.radix.root.children.values():
        stack = [node]
        while stack:
            n = stack.pop()
            frozen[n.page_id] = {
                leaf: np.asarray(b._cache[leaf])[:, n.page_id].copy()
                for leaf in ("k", "v")}
            stack.extend(n.children.values())
    assert frozen  # the prompt registered
    res.update(b.run([Request(uid="r1", prompt=forks[1], max_new_tokens=6),
                      Request(uid="r2", prompt=forks[2],
                              max_new_tokens=6)]))
    got = {u: r.tokens for u, r in res.items()}
    assert got == want  # sharing + COW invisible in the output
    assert ep.paged.cow_copies >= 1  # and COW actually fired
    for pid, leaves in frozen.items():
        for leaf, before in leaves.items():
            np.testing.assert_array_equal(
                np.asarray(b._cache[leaf])[:, pid], before,
                err_msg=f"shared page {pid} leaf {leaf} mutated")


def test_prefix_cache_off_still_pages():
    """prefix_cache=False: pure paging — no sharing, no trie retention,
    generations still identical."""
    cfg = _cfg(prefix_cache=False)
    ec = InferenceEngine(cfg, slots=3, max_seq_len=MAX_LEN,
                         kv_layout="contiguous")
    ep = InferenceEngine(cfg, slots=3, max_seq_len=MAX_LEN,
                         kv_layout="paged", kv_page_len=PAGE)
    params = ec.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    want, _ = _generate(ec, params)
    got, bp = _generate(ep, params)
    assert got == want
    s = bp.stats()
    assert s["prefix_hits"] == 0 and s["kv_pages_live"] == 0  # all freed


# --------------------------------------------------------------------------- #
# admission: page pricing, shed-not-corrupt, serve 429
# --------------------------------------------------------------------------- #


def test_out_of_pages_sheds_and_spares_live_slots():
    """A pool sized for ~one request: the oversized request sheds at the
    door, the waiting request is admitted only after the live one frees
    its pages — and the live slot's stream is untouched either way."""
    cfg = _cfg()
    ec = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                         kv_layout="contiguous")
    # 5 usable pages = 40 rows: request a (commitment 16 tokens = 2
    # pages) and request b (commitment 2 pages) fit only serially once
    # a's radix-retained pages are accounted
    ep = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                         kv_layout="paged", kv_page_len=PAGE,
                         kv_num_pages=6)
    params = ec.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    reqs = [Request(uid="a", prompt=list(range(1, 9)), max_new_tokens=8),
            # needs ceil(64/8) = 8 pages > 5 usable: can NEVER fit
            Request(uid="big", prompt=list(range(1, 33)),
                    max_new_tokens=64),
            Request(uid="b", prompt=[41, 42, 43], max_new_tokens=8)]
    want, _ = _generate(ec, params, prompts=[reqs[0].prompt],
                        max_new=8)
    b = ContinuousBatcher(ep, params, seed=0)
    res = b.run(reqs)
    assert res["big"].finish_reason == "shed"
    assert res["a"].finish_reason == "length"
    assert res["a"].tokens == want["r0"]  # live slot never corrupted
    assert res["b"].finish_reason == "length" and len(res["b"].tokens) == 8
    assert b.counters["shed"] == 1 and b.counters["completed"] == 2


def test_serve_429_reflects_pool_pressure():
    """The HTTP admission path prices in pages: a request beyond the
    pool's capacity is a 429 whose Retry-After scales with the page
    deficit, and /statz surfaces the pool + prefix stats."""
    from picotron_tpu.tools import serve

    cfg = _cfg()
    engine = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                             kv_layout="paged", kv_page_len=PAGE,
                             kv_num_pages=5)  # 4 usable pages
    params = engine.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    srv = serve.Server(engine, params, port=0,
                       log=lambda *a, **k: None)
    srv.start()
    try:
        port = srv.port
        # commitment 8 + 56-cap -> 64 tokens = 8 pages > 4 usable: 429
        st, body = serve._post(port, {"prompt": list(range(1, 9)),
                                      "max_new_tokens": 100})
        assert st == 429 and body["shed"]
        # a mildly-over request backs off less than a hugely-over one
        import http.client

        def retry_after(spec):
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
            conn.request("POST", "/generate", serve.json.dumps(spec),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 429
            ra = int(resp.getheader("Retry-After"))
            resp.read()
            conn.close()
            return ra
        mild = retry_after({"prompt": list(range(1, 9)),
                            "max_new_tokens": 33})  # 6 pages, deficit 2
        huge = retry_after({"prompt": list(range(1, 9)),
                            "max_new_tokens": 100})  # 8 pages, deficit 4
        assert 1 <= mild <= huge
        # a fitting request serves; /statz carries the pool fields
        st, body = serve._post(port, {"prompt": [1, 2, 3],
                                      "max_new_tokens": 4})
        assert st == 200 and body["finish_reason"] == "length"
        st, stats = serve._get(port, "/statz")
        assert stats["rejected"]["page_budget"] == 3
        assert stats["kv_layout"] == "paged"
        assert stats["kv_pages_total"] == 4
        assert 0.0 <= stats["kv_pool_utilization"] <= 1.0
        assert "prefix_hit_rate" in stats and "cow_copies" in stats
    finally:
        srv.drain_and_join(timeout=60)


def test_kv_layout_validated():
    cfg = _cfg()
    with pytest.raises(ValueError, match="kv_layout"):
        InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                        kv_layout="vmem")
    with pytest.raises(ValueError, match="kv_page_len"):
        InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                        kv_layout="paged", kv_page_len=12)
    from picotron_tpu.config import Config

    raw = cfg.to_dict()
    raw["inference"]["kv_layout"] = "vmem"
    with pytest.raises(ValueError, match="kv_layout"):
        Config.from_dict(raw)
    raw["inference"]["kv_layout"] = "paged"
    raw["inference"]["kv_page_len"] = 12
    with pytest.raises(ValueError, match="kv_page_len"):
        Config.from_dict(raw)


def test_cache_lost_rebuild_resets_pool():
    """The batcher's cache-lost path rebuilds via engine.init_cache —
    which must reset the allocator too, or the fresh zeroed pool would
    disagree with stale refcounts/tables."""
    cfg, ec, ep, params = _engines()
    _generate(ep, params, prompts=[_PROMPTS[0]], max_new=4)
    assert ep.paged.pool.live_count > 0  # radix retained the prompt
    cache = ep.init_cache()
    p = ep.paged
    assert p.pool.free_count == p.pool.usable_pages
    assert np.all(p.tables == NULL_PAGE) and np.all(p.host_len == 0)
    assert p.radix.evictable_count() == 0
    del cache
