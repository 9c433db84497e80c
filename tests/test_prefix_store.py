"""The contiguous layout's prefix store (ISSUE 49; ``paged_kv.PrefixStore``,
``kv_cache.retain_rows`` / ``seat_rows``, ``engine.prefill_stored``).

- **the hit is invisible**: greedy and seeded-sampling generations through a
  hit are token for token those of the same requests on an engine with the
  store off, and the rows copied into the slot are byte for byte the rows
  the first request wrote (the standard tests/test_paged_kv.py holds the
  paged layout's sharing to);
- **capacity**: a pool smaller than the documents evicts least recently
  used leaves, never a page in the middle of a retained path, and a dry
  pool retains nothing and fails nothing;
- **where it is absent**: every block but Llama's, ``kv_layout: paged`` and
  each mode not wired to it build no store, and the batcher then takes the
  branches it took before;
- **what the counters say**: ``picotron_prefill_tokens_total`` leaves the
  copied tokens out, the store's own counters count them, and after the
  engine's first cache no admission compiles anything;
- **the narrow chunk** (ISSUE 61; ``engine.NARROW_CHUNK``, ``chunk_width``,
  ``prefill_widths``, ``build_narrow``): behind the store a chunk of at most
  128 real rows is dispatched 128 wide and gives the store-less engine's
  tokens, the hit is weighed in rows, the first batcher builds the second
  shape, and an engine without the store runs one width.
"""

import contextlib
import json
import os

import numpy as np
import pytest

import jax

from conftest import make_config
from picotron_tpu.config import Config
from picotron_tpu.inference import ContinuousBatcher, InferenceEngine, Request
from picotron_tpu.inference.engine import NARROW_CHUNK
from picotron_tpu.inference.paged_kv import NULL_PAGE, PrefixStore
from picotron_tpu.models import llama

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_LEN, PAGE, CHUNK = 64, 8, 16

_TINY = dict(
    num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
    hidden_size=64, intermediate_size=128, vocab_size=256,
    max_position_embeddings=MAX_LEN, rope_theta=10000.0, dtype="float32",
    attention_impl="sdpa")


def _engine(store_pages=0, cfg=None, **kw):
    cfg = cfg or make_config(dict(_TINY), seq=32)
    kw = {"slots": 2, "max_seq_len": MAX_LEN, "kv_page_len": PAGE,
          "prefill_chunk": CHUNK, "decode_block_len": 4,
          "kv_store_pages": store_pages, **kw}
    return cfg, InferenceEngine(cfg, **kw)


def _params(cfg, engine):
    return engine.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))


def _doc(n, base=1):
    return [base + (7 * i) % 200 for i in range(n)]


# (first prompt, second prompt, tokens the second finds retained)
CASES = {
    # the first at or under prefill_chunk, through the one-shot program
    "one_shot": (_doc(12) + [250], _doc(12) + [251] * 9, 8),
    # over prefill_chunk, the retained prefix ends on a chunk's edge
    "chunked": (_doc(32) + [250, 251, 252], _doc(32) + [253] * 4, 32),
    # ... and inside a chunk
    "mid_chunk": (_doc(40) + [250, 251, 252], _doc(40) + [253] * 5, 40),
    # a prefix past max_seq_len - prefill_chunk: the resumed chunk's window
    # slides back and feeds rows [48, 56) again
    "slid_window": (_doc(56) + [250, 251, 252], _doc(56) + [253] * 4, 56),
    # the very prompt again, whole pages long: the cap leaves its last page
    # to prefill, for the first token's logits
    "equal": (_doc(48), _doc(48), 40),
}


@contextlib.contextmanager
def _compiles():
    """The backend compiles made inside the block, one entry each."""
    import jax.monitoring

    seen = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


def _generate(engine, params, requests, seed=0):
    b = ContinuousBatcher(engine, params, seed=seed)
    out = {}
    for r in requests:  # one after the other: the second finds the first
        out.update(b.run([r]))
    return {u: r.tokens for u, r in out.items()}, b


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("case", sorted(CASES))
def test_generation_through_a_hit_is_the_store_off_engines(case, temperature):
    first, second, cached = CASES[case]
    reqs = [Request(uid=u, prompt=p, max_new_tokens=6,
                    temperature=temperature, top_k=20)
            for u, p in (("first", first), ("second", second))]
    cfg, off = _engine(store_pages=-1)
    _, on = _engine(cfg=cfg)
    assert off.store is None and on.store is not None
    params = _params(cfg, on)
    want, b_off = _generate(off, params, reqs)
    got, b_on = _generate(on, params, reqs)
    assert got == want
    s = b_on.stats()
    assert s["prefix_hits"] == 1 and s["prefix_cached_tokens"] == cached
    assert b_on._last_prefill["cached_tokens"] == cached
    # the counter the benchmark's reuse reader takes leaves the copy out
    ran = lambda b: b.obs.registry.counter(
        "picotron_prefill_tokens_total").value
    assert ran(b_off) == len(first) + len(second)
    assert ran(b_on) == len(first) + len(second) - cached
    reg = on.obs.registry
    assert reg.counter("picotron_prefix_store_hits_total").value == 1
    assert reg.counter(
        "picotron_prefix_store_tokens_copied_total").value == cached
    assert reg.counter("picotron_prefix_store_pages_retained_total").value \
        == s["prefix_store_pages_retained"] == s["prefix_store_pages_live"]
    assert reg.gauge("picotron_prefix_store_bytes").value \
        == on.store_bytes == s["prefix_store_bytes"]


@pytest.mark.parametrize("case", sorted(CASES))
def test_copied_rows_are_the_rows_the_first_request_wrote(case):
    first, second, cached = CASES[case]
    cfg, eng = _engine()
    params = _params(cfg, eng)
    cache = eng.init_cache()
    cache, _, n, none = eng.prefill_stored(params, cache, first, 0)
    assert none == 0 and n == (1 if len(first) <= CHUNK
                               else -(-len(first) // CHUNK))
    wrote = {leaf: np.asarray(cache[leaf])[:, 0, :cached].copy()
             for leaf in ("k", "v")}
    # the retention is owed until a round is on the device
    assert eng._store_seat(cache, second, 1)[1] == 0
    eng._store_flush(cache)
    assert not eng._store_pending
    cache, got = eng._store_seat(cache, second, 1)
    assert got == cached and int(np.asarray(cache["lengths"])[1]) == cached
    for leaf, rows in wrote.items():
        np.testing.assert_array_equal(
            np.asarray(cache[leaf])[:, 1, :cached], rows,
            err_msg=f"{case}: leaf {leaf}")
        # the first request's own strip is as it was
        np.testing.assert_array_equal(
            np.asarray(cache[leaf])[:, 0, :cached], rows)


@pytest.mark.parametrize("second", [
    _doc(12) + [251, 252],  # the one-shot program takes it in one bucket
    _doc(8) + [251] * 17,   # 17 tokens left: still two chunks of 16
], ids=["one_shot", "as_many_chunks"])
def test_a_hit_that_saves_no_dispatch_is_not_taken(second):
    """A resumed suffix pays whole chunks, so the retained page is copied
    in only where that leaves fewer prefill dispatches than the prompt
    takes whole: else the prompt runs the store-less engine's programs."""
    reqs = [Request(uid="first", prompt=_doc(12) + [250], max_new_tokens=6),
            Request(uid="second", prompt=second, max_new_tokens=6)]
    cfg, off = _engine(store_pages=-1)
    _, on = _engine(cfg=cfg)
    params = _params(cfg, on)
    want, b_off = _generate(off, params, reqs)
    got, b_on = _generate(on, params, reqs)
    assert got == want
    assert b_on.prefill_dispatches == b_off.prefill_dispatches
    s = b_on.stats()
    assert s["prefix_queries"] == 2 and s["prefix_hits"] == 0
    assert on.obs.registry.counter(
        "picotron_prefix_store_hits_total").value == 0


def test_two_tenants_with_one_document_share_nothing():
    doc = _doc(40)
    reqs = [Request(uid=u, prompt=doc + [q], max_new_tokens=4, tenant=t)
            for u, t, q in (("a0", "a", 250), ("b0", "b", 251),
                            ("a1", "a", 252))]
    cfg, off = _engine(store_pages=-1)
    _, on = _engine(cfg=cfg)
    params = _params(cfg, on)
    want, _ = _generate(off, params, reqs)
    b = ContinuousBatcher(on, params, seed=0)
    got, cached = {}, {}
    for r in reqs:
        got.update({u: x.tokens for u, x in b.run([r]).items()})
        cached[r.uid] = b._last_prefill["cached_tokens"]
    assert got == want
    # tenant b prefills the document tenant a retained; a's next ask hits
    assert cached == {"a0": 0, "b0": 0, "a1": 40}
    assert b.stats()["prefix_store_pages_live"] == 10


def test_a_prompt_no_round_followed_is_retained_before_its_slot_is_reused():
    """A request that ends at its first token leaves its retention owed
    and no round to copy it behind: the next prompt into that slot settles
    it first, from rows it is about to overwrite; never from its own."""
    doc_a, doc_b = _doc(40, 1), _doc(40, 2)
    reqs = [Request(uid="a", prompt=doc_a + [250], max_new_tokens=1),
            Request(uid="b", prompt=doc_b + [251], max_new_tokens=1),
            Request(uid="a2", prompt=doc_a + [252], max_new_tokens=6),
            Request(uid="b2", prompt=doc_b + [253], max_new_tokens=6)]
    cfg, off = _engine(store_pages=-1, slots=1)
    _, on = _engine(cfg=cfg, slots=1)
    params = _params(cfg, on)
    want, _ = _generate(off, params, reqs)
    got, b = _generate(on, params, reqs)
    assert got == want
    assert b.stats()["prefix_hits"] == 2
    assert b.stats()["prefix_cached_tokens"] == 80


def _path(store, ids):
    """The trie's nodes along ``ids``' whole pages, as far as they go."""
    node, out = store.radix.root, []
    for i in range(len(ids) // store.page_len):
        node = node.children.get(
            tuple(ids[i * store.page_len:(i + 1) * store.page_len]))
        if node is None:
            break
        out.append(node)
    return out


def _retain(store, ids):
    plan = store.plan_retain(ids)
    if plan is None:
        return None
    row, chunk_pids = plan
    assert sorted(p for p in row if p != NULL_PAGE) \
        == sorted(chunk_pids.values())
    store.commit(ids, chunk_pids)
    return chunk_pids


def test_a_small_pool_evicts_lru_leaves_and_never_cuts_a_path():
    store = PrefixStore(PAGE, MAX_LEN // PAGE, 1 + 6)
    a, b, c = _doc(32, 1), _doc(32, 2), _doc(32, 3)
    assert sorted(_retain(store, a)) == [0, 1, 2, 3]
    # b needs four pages, two are free: a's two LAST pages go, leaf first
    assert sorted(_retain(store, b)) == [0, 1, 2, 3]
    assert store.radix.evictions == 2
    assert len(_path(store, a)) == 2 and len(_path(store, b)) == 4
    assert store.lookup(a + [9])[1] == 16  # a's leading pages still serve
    # a was just used, so c's pages come off b's end, then off the rest
    assert sorted(_retain(store, c)) == [0, 1, 2, 3]
    assert len(_path(store, c)) == 4
    kept = len(_path(store, a)) + len(_path(store, b))
    assert kept == 2 and store.pool.free_count == 0
    # what is left of every path is a path: root to leaf, no gap
    for ids in (a, b, c):
        for i, node in enumerate(_path(store, ids)):
            assert node.tokens == tuple(ids[i * PAGE:(i + 1) * PAGE])
            assert store.pool.refs[node.page_id] == 1
    # extending a retained path evicts around it, not into it
    longer = c + _doc(16, 5)
    assert sorted(_retain(store, longer)) == [4, 5]
    assert len(_path(store, longer)) == 6


def test_a_dry_pool_retains_what_fits_then_nothing_and_never_raises():
    store = PrefixStore(PAGE, MAX_LEN // PAGE, 1 + 2)
    a = _doc(32, 1)
    assert sorted(_retain(store, a)) == [0, 1]  # the leading pages
    held = [n.page_id for n in _path(store, a)]
    for pid in held:  # someone holds them: nothing is evictable
        store.pool.ref(pid)
    assert _retain(store, _doc(32, 2)) is None
    assert store.pool.free_count == 0 and len(_path(store, a)) == 2
    store.release_pages(held)
    assert store.lookup(a)[1] == 16
    assert store.lookup(_doc(7))[1] == 0  # under a page: nothing to find


def test_generations_with_a_pool_smaller_than_the_documents():
    docs = [_doc(40, 1), _doc(40, 2), _doc(40, 3)]
    reqs = [Request(uid=f"r{i}", prompt=docs[i % 3] + [250 - i],
                    max_new_tokens=4) for i in range(7)]
    cfg, off = _engine(store_pages=-1)
    _, on = _engine(store_pages=1 + 8, cfg=cfg)  # a document and a half
    params = _params(cfg, on)
    want, _ = _generate(off, params, reqs)
    got, b = _generate(on, params, reqs)
    assert got == want
    s = b.stats()
    assert s["radix_evictions"] > 0 and s["prefix_store_pages_live"] <= 8
    assert on.obs.registry.counter(
        "picotron_prefix_store_pages_evicted_total").value \
        == s["radix_evictions"]
    _, dry = _engine(store_pages=2, cfg=cfg)  # one page: next to nothing
    got, b = _generate(dry, params, reqs)
    assert got == want and b.counters["completed"] == 7


def test_a_failed_retention_costs_the_store_and_not_the_request(monkeypatch):
    cfg, eng = _engine()
    params = _params(cfg, eng)
    b = ContinuousBatcher(eng, params, seed=0)
    one = lambda u, p: b.run([Request(uid=u, prompt=p, max_new_tokens=5)])
    one("a", _doc(40, 1) + [250])
    assert b.stats()["prefix_store_pages_live"] == 5
    real = eng._retain_jit

    def fails(*args):
        raise RuntimeError("the device said no")

    monkeypatch.setattr(eng, "_retain_jit", fails)
    assert len(one("b", _doc(40, 2) + [250])["b"].tokens) == 5
    assert b.stats()["prefix_store_pages_live"] == 0  # it started over
    monkeypatch.setattr(eng, "_retain_jit", real)
    one("b2", _doc(40, 2) + [251])  # nothing of b was kept: prefilled whole
    assert b._last_prefill["cached_tokens"] == 0
    one("b3", _doc(40, 2) + [252])
    assert b._last_prefill["cached_tokens"] == 40


def test_no_admission_compiles_after_the_first_cache():
    """Both copy programs are compiled with the engine's first cache, under
    the cache's own shardings whoever made the arrays: hits and retentions
    behind every producer of a cache (a one-shot insert, a chunk, a decode
    round, a release) compile nothing, as the benchmark's window demands."""
    with _compiles() as compiles:
        cfg, eng = _engine()
        params = _params(cfg, eng)
        doc = _doc(40)
        prompts = [doc + [250], _doc(9, 3), doc + [251, 252], _doc(30, 4),
                   doc[:24] + [253], _doc(9, 3) + [254]]
        run = lambda b, ps: b.run([
            Request(uid=f"r{i}", prompt=p, max_new_tokens=5)
            for i, p in enumerate(ps)])
        b = ContinuousBatcher(eng, params, seed=0)  # the first cache
        assert len(compiles) >= 2
        # the warm-up the benchmark makes: every prefill shape, no hit
        run(b, [_doc(9, 5), _doc(30, 6)])
        assert b.stats()["prefix_hits"] == 0
        warm = len(compiles)
        run(b, prompts)
        assert b.stats()["prefix_hits"] == 2
        assert len(compiles) == warm


def _cell_model(name):
    """A benchmark configuration's toy model section (its ``rehearsal``)."""
    from benchmarks import common
    from benchmarks.run import merged

    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        config = json.load(f)
    return common.model_section(merged(config, config.get("rehearsal", {})))


OTHER_BLOCKS = sorted(
    f for f in os.listdir(os.path.join(ROOT, "benchmarks", "configs"))
    if not f.startswith(("smollm", "mistral")))


@pytest.mark.parametrize("config", OTHER_BLOCKS)
def test_no_store_beside_another_blocks_cache(config):
    model = _cell_model(config)
    assert model["model_type"] != "llama"
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True}, "model": model,
        "training": {"seq_length": 64}, "dataset": {"name": "synthetic"}})
    eng = InferenceEngine(cfg, slots=2, max_seq_len=128)
    assert eng.store is None and eng._store_pool is None


@pytest.mark.parametrize("mode", [
    dict(kv_layout="paged"), dict(cache_dtype="int8"), dict(spec_len=2),
    dict(overlap=True), dict(mixed_dispatch=True), dict(kv_store_pages=-1)])
def test_no_store_in_a_mode_not_wired_to_it(mode, monkeypatch):
    cfg, eng = _engine(**mode)
    assert eng.store is None
    monkeypatch.setattr(
        InferenceEngine, "prefill_stored",
        lambda *a, **k: pytest.fail("the store-less engine was admitted "
                                    "through the store"))
    params = _params(cfg, eng)
    b = ContinuousBatcher(eng, params, seed=0)
    assert eng._store_pool is None
    res = b.run([Request(uid="r", prompt=_doc(20), max_new_tokens=3)])
    assert len(res["r"].tokens) == 3
    assert "prefix_store_bytes" not in b.stats()


def test_no_store_on_a_dp_sharded_engine():
    cfg = make_config(dict(_TINY), seq=32)
    cfg.inference.dp_size = 2
    eng = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN)
    assert eng.store is None


def test_auto_size_holds_twice_the_strips_and_shrinks_to_the_device(
        monkeypatch):
    cfg, eng = _engine()
    pages = MAX_LEN // PAGE
    assert eng.store.num_pages == 1 + 2 * 2 * pages
    # a page is PAGE rows of a strip, every leaf and layer of it
    rows = eng.slots * MAX_LEN
    assert eng.store_bytes * rows == (
        eng.kv_cache_bytes - 4 * eng.slots) * eng.store.num_pages * PAGE
    shapes = jax.eval_shape(eng._init_cache_jit)
    page_bytes = eng.store_bytes // eng.store.num_pages
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        jax.eval_shape(lambda: llama.init_params(jax.random.key(0),
                                                 cfg.model))))

    def fits(limit):
        monkeypatch.setattr(eng, "_device_limit", lambda: limit)
        return eng._store_pages(shapes, PAGE)

    taken = weights + eng.kv_cache_bytes
    # an eighth of the device is left to the programs
    roomy = (taken + 40 * page_bytes) * 8 // 7 + 8
    assert fits(roomy) == 1 + 2 * 2 * pages
    assert fits((taken + 5 * page_bytes) * 8 // 7 + 8) == 1 + 5
    assert fits(taken) == 0  # strips that fill the chip: as without a store


# ---- the narrow chunk (ISSUE 61) -------------------------------------------
# a chunk wider than NARROW_CHUNK, which the tests above never have
WIDE, LONG = 2 * NARROW_CHUNK, 4 * NARROW_CHUNK


def _wide_engine(store_pages=0, cfg=None, **kw):
    return _engine(store_pages, cfg, **{"max_seq_len": LONG,
                                        "prefill_chunk": WIDE, **kw})


def _widths(engine):
    """The chunk program's dispatches from here on, by the width of their
    operand: what ran, not what the counters say ran."""
    seen, real = [], engine._prefill_chunk_jit

    def spy(params, cache, tokens, *rest):
        seen.append(tokens.shape[1])
        return real(params, cache, tokens, *rest)

    engine._prefill_chunk_jit = spy
    return seen


def _counted(engine):
    reg = engine.obs.registry
    return (reg.counter("picotron_prefill_rows_total").value,
            {w: c.value for w, c in engine.prefill_chunks_total.items()})


@pytest.mark.parametrize("suffix", [1, 17, 59, NARROW_CHUNK,
                                    NARROW_CHUNK + 1])
def test_a_resumed_suffix_runs_a_chunk_of_its_own_width(suffix):
    """A suffix of at most NARROW_CHUNK rows behind a hit is dispatched that
    wide, a longer one as wide as ever, and both give the store-less
    engine's tokens."""
    doc = _doc(136)  # 17 pages: whole, it would take the 256-row bucket
    reqs = [Request(uid="first", prompt=doc + [250], max_new_tokens=6),
            Request(uid="second", prompt=doc + [251] * suffix,
                    max_new_tokens=6)]
    cfg, off = _wide_engine(store_pages=-1)
    _, on = _wide_engine(cfg=cfg)
    assert off.narrow_chunk == 0 and on.narrow_chunk == NARROW_CHUNK
    params = _params(cfg, on)
    want, _ = _generate(off, params, reqs)
    b = ContinuousBatcher(on, params, seed=0)
    b.run(reqs[:1])  # the first ask: one-shot, a 256-row bucket
    seen, (rows0, chunks0) = _widths(on), _counted(on)
    got = {"first": want["first"],
           "second": b.run(reqs[1:])["second"].tokens}
    assert got == want
    assert b._last_prefill == {"dispatches": 1, "cached_tokens": 136}
    width = NARROW_CHUNK if suffix <= NARROW_CHUNK else WIDE
    assert seen == [width]
    rows, chunks = _counted(on)
    assert rows - rows0 == width
    assert chunks[width] - chunks0[width] == 1
    s = b.stats()
    assert s["prefill_rows"] == rows
    assert s["prefill_chunks"] == {str(w): int(n) for w, n in chunks.items()}
    assert s["prefill_tokens"] == 137 + suffix


@pytest.mark.parametrize("tail", [1, 44, NARROW_CHUNK, NARROW_CHUNK + 1])
def test_a_first_asks_short_last_chunk_runs_narrow_and_changes_nothing(tail):
    """Nothing retained: a prompt past a chunk ends in a chunk of ``tail``
    rows, narrow if they fit, and the tokens are the all-wide engine's."""
    reqs = [Request(uid="r", prompt=_doc(WIDE + tail), max_new_tokens=6)]
    cfg, off = _wide_engine(store_pages=-1)
    _, on = _wide_engine(cfg=cfg)
    params = _params(cfg, on)
    wide, narrow = _widths(off), _widths(on)
    want, _ = _generate(off, params, reqs)
    got, b = _generate(on, params, reqs)
    assert got == want
    assert wide == [WIDE, WIDE]
    # the batcher's build of the narrow program first, then the prompt's
    assert narrow == [NARROW_CHUNK, WIDE,
                      NARROW_CHUNK if tail <= NARROW_CHUNK else WIDE]
    assert b.prefill_dispatches == 2 and b.stats()["prefix_hits"] == 0


@pytest.mark.parametrize("n, cached, widths, whole", [
    # fully retained, and still its one-shot bucket: 64 rows beat 128
    (64, 0, [64], [64]),
    # a 128-row bucket against a 128-row chunk: no fewer, not taken
    (100, 0, [128], [128]),
    # a 256-row bucket against a 128-row chunk: taken
    (137, 136, [NARROW_CHUNK], [256]),
    # 300 tokens: a chunk and a narrow one whole, one narrow one behind a hit
    (300, 296, [NARROW_CHUNK], [WIDE, NARROW_CHUNK]),
], ids=["bucket_64", "bucket_128", "bucket_256", "chunked_300"])
def test_a_hit_is_worth_the_rows_it_saves(n, cached, widths, whole):
    """The hit is taken where it leaves the programs fewer rows to run,
    padding included, than the prompt prefilled whole."""
    prompt = _doc(n)
    reqs = [Request(uid=u, prompt=prompt, max_new_tokens=4)
            for u in ("first", "again")]
    cfg, off = _wide_engine(store_pages=-1)
    _, on = _wide_engine(cfg=cfg)
    params = _params(cfg, on)
    assert on.prefill_widths(n) == whole
    want, _ = _generate(off, params, reqs)
    b = ContinuousBatcher(on, params, seed=0)
    got = {"first": b.run(reqs[:1])["first"].tokens}
    rows0, _ = _counted(on)
    got["again"] = b.run(reqs[1:])["again"].tokens
    assert got == want
    assert b._last_prefill == {"dispatches": len(widths),
                               "cached_tokens": cached}
    assert on.prefill_widths(n, cached) == widths
    assert _counted(on)[0] - rows0 == sum(widths)
    assert b.stats()["prefix_hits"] == (1 if cached else 0)


def test_a_narrow_chunk_near_the_strips_end_stays_inside_it():
    """A resumed suffix ending within NARROW_CHUNK rows of ``max_seq_len``:
    the window slides back by the width dispatched, feeds the rows before
    the suffix again, and every row of the strip is the store-less
    engine's."""
    doc = _doc(LONG - 32)
    first, second = doc + [250], doc + [251] * 20
    cfg, off = _wide_engine(store_pages=-1)
    _, on = _wide_engine(cfg=cfg)
    params = _params(cfg, on)
    cache = on.init_cache()
    cache = on.prefill_stored(params, cache, first, 0)[0]
    on._store_flush(cache)
    seen = _widths(on)
    starts, real = [], on._chunk

    def spy(params, cache, padded, slot, w0, valid, samp):
        starts.append((w0, valid))
        return real(params, cache, padded, slot, w0, valid, samp)

    on._chunk = spy
    cache, logits, n, cached = on.prefill_stored(params, cache, second, 1)
    assert (n, cached, seen) == (1, LONG - 32, [NARROW_CHUNK])
    # rows [LONG - 128, LONG - 12): 96 of them fed again, 20 new
    assert starts == [(LONG - NARROW_CHUNK, NARROW_CHUNK - 12)]
    ref, want = off.prefill_chunked(params, off.init_cache(), second, 1)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert int(np.asarray(cache["lengths"])[1]) == len(second)
    for leaf in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache[leaf])[:, 1, :len(second)],
            np.asarray(ref[leaf])[:, 1, :len(second)],
            rtol=1e-5, atol=1e-5, err_msg=leaf)


def test_the_first_batcher_builds_the_narrow_program_and_no_second_does():
    """``build_narrow`` runs with the first batcher's cache: a resumed
    admission, the first short suffix anyone sends, compiles nothing, the
    warm-up dispatch leaves no trace in the counters or the slot, and a
    second batcher (a rebuilt cache) dispatches nothing."""
    with _compiles() as compiles:
        cfg, eng = _wide_engine()
        params = _params(cfg, eng)
        seen = _widths(eng)
        b = ContinuousBatcher(eng, params, seed=0)
        assert seen == [NARROW_CHUNK] and eng._narrow_built
        assert _counted(eng) == (0, {WIDE: 0, NARROW_CHUNK: 0})
        assert not np.asarray(b._cache["lengths"]).any()
        run = lambda ps: b.run([
            Request(uid=f"r{i}", prompt=p, max_new_tokens=5)
            for i, p in enumerate(ps)])
        # the benchmark's warm-up: fresh prompts, every one-shot bucket and
        # whole chunks, so no chunk of it is short
        run([_doc(n, 5 + i) for i, n in enumerate((9, 20, 40, 70, 130))]
            + [_doc(2 * WIDE - 8, 4)])
        assert set(seen) == {NARROW_CHUNK, WIDE}
        assert b.stats()["prefix_hits"] == 0
        warm, doc = len(compiles), _doc(200, 20)
        for prompt in (doc + [250], doc + [251] * 30, _doc(WIDE + 40, 30)):
            run([prompt])
        assert b.stats()["prefix_hits"] == 1
        assert _counted(eng)[1][NARROW_CHUNK] == 2
        assert len(compiles) == warm
        del seen[:]
        ContinuousBatcher(eng, params, seed=1)
        assert seen == [] and len(compiles) == warm


def _other_block_engine():
    """One other block's toy configuration (the Granite cell's rehearsal),
    its window two chunks of 256 rows."""
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True},
        "model": _cell_model("granite-4.0-h-small-ep2-l10.json"),
        "training": {"seq_length": 64}, "dataset": {"name": "synthetic"}})
    eng = InferenceEngine(cfg, slots=2, max_seq_len=LONG,
                          prefill_chunk=WIDE)
    return eng, eng.shard_params(jax.jit(
        lambda k: eng.model.init_params(k, cfg.model))(jax.random.PRNGKey(0)))


def _llama_engine(**mode):
    cfg, eng = _wide_engine(**mode)
    return eng, _params(cfg, eng)


@pytest.mark.parametrize("build", [
    lambda: _llama_engine(store_pages=-1),
    lambda: _llama_engine(kv_layout="paged"),
    _other_block_engine], ids=["store_off", "paged", "granite"])
def test_an_engine_without_the_store_never_runs_a_narrow_chunk(build):
    eng, params = build()
    assert eng.store is None and eng.narrow_chunk == 0
    assert list(eng.prefill_chunks_total) == [WIDE]
    assert eng.chunk_width(1) == WIDE
    assert eng.prefill_widths(WIDE + 30) == [WIDE, WIDE]
    seen = _widths(eng)
    b = ContinuousBatcher(eng, params, seed=0)
    assert seen == []  # nothing to build
    vocab = eng.cfg.model.vocab_size
    prompt = [1 + (7 * i) % (vocab - 1) for i in range(WIDE + 30)]
    res = b.run([Request(uid="r", prompt=prompt, max_new_tokens=3)])
    assert len(res["r"].tokens) == 3
    assert seen == [WIDE, WIDE]
    assert b.stats()["prefill_chunks"] == {str(WIDE): 2}
    assert b.stats()["prefill_rows"] == 2 * WIDE


@pytest.mark.parametrize("before, after, want", [
    # 600 of 2,048 rows held a prompt token
    ("picotron_prefill_tokens_total 1000\npicotron_prefill_rows_total 2048",
     "picotron_prefill_tokens_total 1600\npicotron_prefill_rows_total 4096",
     100.0 * (1 - 600 / 2048)),
    # no prefill in the window
    ("picotron_prefill_tokens_total 7\npicotron_prefill_rows_total 16",
     "picotron_prefill_tokens_total 7\npicotron_prefill_rows_total 16", None),
    # a program without the counter: the parent under this benchmark
    ("picotron_prefill_tokens_total 1000",
     "picotron_prefill_tokens_total 1600", None),
], ids=["padded", "idle", "parent"])
def test_the_pad_rows_reader_on_two_scrapes(before, after, want):
    from benchmarks.run import load_reader

    read = load_reader("layer_metrics", "engine.prefill_pad_rows_pct.chat")
    got = read({"metrics_before": before, "metrics_after": after})
    assert got == (want if want is None else pytest.approx(want))
    assert read({}) is None
