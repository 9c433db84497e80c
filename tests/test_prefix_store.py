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
  engine's first cache no admission compiles anything.
"""

import json
import os

import numpy as np
import pytest

import jax

from conftest import make_config
from picotron_tpu.config import Config
from picotron_tpu.inference import ContinuousBatcher, InferenceEngine, Request
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
    import jax.monitoring

    compiles = []

    def on(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
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
    finally:
        jax.monitoring.unregister_event_duration_listener(on)


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
