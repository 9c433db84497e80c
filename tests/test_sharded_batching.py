"""dp-sharded continuous batching (ISSUE 18): ONE logical engine whose
slot axis spans the dp mesh axis.

Acceptance surface:
- dp=2 greedy generations are BIT-IDENTICAL to dp=1 across the program
  families (blocked decode, speculative verify, chunked prefill), attend
  kernels (dense/flash), KV layouts (contiguous/paged), int8 KV cache and
  int8 weights, and mixed-tenant batches — on tp=1 and a tp=2 mesh
  (dp x tp devices out of the forced 8-device CPU host platform);
- a forced cross-shard slot migration (engine.migrate_slot: one batched
  page gather + one donating write through the page-transport device
  path) resumes decode bit-identically, with page refcounts conserved;
- the planner's edge cases hold: a migration attempted after a
  speculative verify exports only ACCEPTED rows (draft garbage past the
  length pointer never travels), destination-pool exhaustion aborts the
  plan with the source slot untouched and refcounts conserved, and a
  dead dp peer discovered mid-migration exits through the ClusterMonitor
  lease path (EXIT_CLUSTER_FAILED) without leaking a single page;
- dp=1 stays the byte-identical default: every construction below also
  runs the dp=1 engine, and the dp=2 run must reproduce it exactly.
"""

import os
import time

import numpy as np
import pytest

import jax

from conftest import make_config
from picotron_tpu.inference import (
    ContinuousBatcher,
    InferenceEngine,
    Request,
)
from picotron_tpu.inference import paged_kv
from picotron_tpu.models import llama
from picotron_tpu.resilience.cluster import (
    EXIT_CLUSTER_FAILED,
    ClusterMonitor,
)

MAX_LEN = 96


def _engine(tiny_model_kwargs, dp, tp=1, slots=4, **kw):
    cfg = make_config(tiny_model_kwargs, tp=tp, seq=MAX_LEN)
    cfg.inference.dp_size = dp
    kw.setdefault("decode_block_len", 4)
    eng = InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN, **kw)
    return cfg, eng


def _params(cfg, engine, seed=0):
    p = jax.jit(lambda k: llama.init_params(k, cfg.model))(
        jax.random.PRNGKey(seed))
    if engine.quant_weights:
        p = llama.quantize_params(p)
    return engine.shard_params(p)


def _skewed_reqs(program):
    """2 long + 2 short greedy requests: shard 0's slots keep decoding
    after shard 1's retire, so a dp=2 batcher sees occupancy skew (and,
    on the paged layout, a rebalance migration) mid-run. ``verify`` uses
    repetitive prompts (the regime prompt-lookup drafting accepts on);
    ``chunked`` uses prompts spanning 2-3 prefill chunks."""
    if program == "verify":
        return [Request("l0", [5, 9, 5, 9, 5, 9], max_new_tokens=20),
                Request("l1", [7, 3, 7, 3, 7, 3, 7], max_new_tokens=20),
                Request("s0", [11, 12, 11, 12], max_new_tokens=4),
                Request("s1", [13, 14, 13, 14], max_new_tokens=4)]
    if program == "chunked":
        long_a = [(5 * i + 2) % 199 + 1 for i in range(20)]
        long_b = [(3 * i + 7) % 199 + 1 for i in range(17)]
        return [Request("l0", long_a, max_new_tokens=16),
                Request("l1", long_b, max_new_tokens=16),
                Request("s0", [11, 12] * 5, max_new_tokens=4),
                Request("s1", [13, 14] * 6, max_new_tokens=4)]
    return [Request("l0", [1, 2, 3, 4, 5], max_new_tokens=24),
            Request("l1", [9, 8, 7, 6], max_new_tokens=24),
            Request("s0", [11, 12], max_new_tokens=4),
            Request("s1", [13, 14, 15], max_new_tokens=4)]


def _run(tiny_model_kwargs, dp, program, **kw):
    if program == "verify":
        kw.setdefault("spec_len", 3)
    if program == "chunked":
        kw.setdefault("prefill_chunk", 8)
    cfg, eng = _engine(tiny_model_kwargs, dp, **kw)
    b = ContinuousBatcher(eng, _params(cfg, eng))
    res = b.run(_skewed_reqs(program))
    return {uid: (r.tokens, r.finish_reason) for uid, r in res.items()}, b


# --------------------------------------------------------------------------- #
# dp=2 == dp=1, across the program/kernel/layout/quantization matrix
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("program,attend,layout,quant,tp", [
    ("block",   "dense", "contiguous", None,     1),
    ("block",   "dense", "paged",      None,     2),
    ("block",   "flash", "paged",      None,     1),
    ("block",   "flash", "contiguous", "int8kv", 2),
    ("block",   "dense", "paged",      "int8w",  1),
    ("verify",  "dense", "contiguous", None,     1),
    ("verify",  "dense", "paged",      "int8kv", 2),
    ("chunked", "dense", "paged",      None,     2),
    ("chunked", "flash", "contiguous", None,     1),
])
def test_dp2_greedy_matches_dp1(tiny_model_kwargs, program, attend,
                                layout, quant, tp):
    """The tentpole gate: the SAME skewed workload through a dp=2 engine
    (slot axis sharded over dp, params replicated across it) produces
    token streams bit-identical to the dp=1 engine — each program family
    crossed with a representative kernel/layout/quantization corner, on
    tp=1 and tp=2. The paged dp=2 runs retire shard 1's short requests
    early, so the rebalance planner is live inside the measured run."""
    kw = dict(attend_impl=attend, kv_layout=layout)
    if quant == "int8kv":
        kw["cache_dtype"] = "int8"
    elif quant == "int8w":
        kw["weight_dtype"] = "int8"
    base, _ = _run(tiny_model_kwargs, 1, program, tp=tp, **kw)
    got, b2 = _run(tiny_model_kwargs, 2, program, tp=tp, **kw)
    assert got == base, (program, attend, layout, quant, tp)
    st = b2.stats()
    assert st["dp_size"] == 2
    assert st["slots_total"] == 2 * b2.engine.slots_per_shard


@pytest.mark.parametrize("program,dp_ops", [
    ("block", set()), ("verify", set()),
    ("chunked", {"prefill_owner_reduce"}),
])
def test_dp2_traces_no_dp_collective_but_the_owner_reduce(
        tiny_model_kwargs, program, dp_ops, monkeypatch, capfd):
    """Every program a dp=2 batcher traces over a whole paged run (the
    rebalance migration included) is shard-local: the comm_trace channel
    logs each collective once as it is traced, and the only line on the
    dp axis is the chunked prefill's owner reduce, which the third case
    shows the channel does see."""
    monkeypatch.setenv("PICOTRON_VERBOSE", "1")
    _, b = _run(tiny_model_kwargs, 2, program, kv_layout="paged",
                kv_page_len=8)
    assert b.decode_dispatches > 0
    if program != "verify":  # the watermark tripped: a migration traced too
        assert b.stats()["rebalance_count"] >= 1
    lines = [ln for ln in capfd.readouterr().err.splitlines()
             if ln.startswith("[comm]") and "axis=dp" in ln]
    assert {ln.split()[1] for ln in lines} == dp_ops, lines


def test_dp2_round_keys_replicated_and_sampled_pinned(tiny_model_kwargs):
    """The round schedule under dp=2: the key program's outputs lie
    replicated over the dp mesh (the round program takes keys as P()), and
    a sampled run draws what it drew while the batcher split eagerly and
    shipped host keys (tokens of PR 28's tree; shard 1's rows differ from a
    dp=1 run's, there as here: each shard draws for its own rows)."""
    cfg, eng = _engine(tiny_model_kwargs, 2)
    assert eng.key_schedule == "round"
    b = ContinuousBatcher(eng, _params(cfg, eng), seed=3)
    res = b.run([Request(f"r{i}", [1 + i, 2 + i, 3 + i], max_new_tokens=10,
                         temperature=0.9, top_k=20) for i in range(4)])
    assert {u: r.tokens for u, r in res.items()} == {
        "r0": [36, 93, 200, 59, 183, 165, 61, 101, 64, 153],
        "r1": [74, 37, 252, 150, 184, 2, 227, 98, 189, 170],
        "r2": [206, 93, 200, 59, 183, 165, 61, 101, 208, 186],
        "r3": [11, 228, 23, 121, 228, 228, 205, 208, 133, 170]}
    key, keys = eng.round_keys(b._key)
    for out in (key, keys):
        assert out.sharding.is_fully_replicated
        assert len(out.sharding.device_set) == 2


def test_dp2_mixed_tenants_match_dp1(tiny_model_kwargs):
    """Mixed-tenant batches (2 LoRA tenants + anonymous base rows in ONE
    continuous batch, per-tenant radix salts) survive the dp split: the
    dp=2 paged engine's per-tenant streams equal the dp=1 engine's."""
    from picotron_tpu.inference import tenancy

    def build(dp):
        c = make_config(tiny_model_kwargs, tp=1, seq=MAX_LEN)
        c.inference.dp_size = dp
        pack = tenancy.AdapterPack(c.model, slots=3, rank=2)
        for t in (1, 2):
            pack.set_slot(t, pack.random_leaves(2, seed=t, scale=0.5))
        eng = InferenceEngine(c, adapters=pack, slots=4,
                              max_seq_len=MAX_LEN, decode_block_len=4,
                              kv_layout="paged")
        return c, eng

    def run(dp):
        c, eng = build(dp)
        b = ContinuousBatcher(eng, _params(c, eng))
        reqs = [Request("a", [1, 2, 3, 4], max_new_tokens=20,
                        tenant="acme", adapter_slot=1),
                Request("b", [9, 8, 7], max_new_tokens=20,
                        tenant="beta", adapter_slot=2),
                Request("c", [11, 12], max_new_tokens=4),
                Request("d", [13, 14, 15], max_new_tokens=4)]
        res = b.run(reqs)
        return {u: r.tokens for u, r in res.items()}

    assert run(2) == run(1)


# --------------------------------------------------------------------------- #
# cross-shard migration: exactness + refcount conservation
# --------------------------------------------------------------------------- #


def _refs_snapshot(p):
    """np copies of every shard pool's refcount array (dp=1: the one
    pool) — the conservation ledger migration tests diff."""
    shards = getattr(p, "shards", None)
    if shards is None:
        return [np.asarray(p.pool.refs).copy()]
    return [np.asarray(sh.pool.refs).copy() for sh in shards]


def _seat(eng, params, cache, slot, prompt):
    kv, logits = eng.prefill(params, prompt)
    cache = eng.insert(cache, kv, slot, len(prompt))
    return cache, int(np.argmax(np.asarray(logits)[0]))


def _decode_rounds(eng, params, cache, last_by_slot, rounds=2):
    """Greedy blocked decode for the occupied slots; returns the per-slot
    token streams. Free slots carry budget 0."""
    n = eng.slots
    streams = {s: [] for s in last_by_slot}
    temp = np.zeros(n, np.float32)
    top_k = np.zeros(n, np.int32)
    top_p = np.ones(n, np.float32)
    eos = np.full(n, -1, np.int32)
    key = jax.random.PRNGKey(0)
    for _ in range(rounds):
        feed = np.zeros(n, np.int32)
        budget = np.zeros(n, np.int32)
        for s, t in last_by_slot.items():
            feed[s], budget[s] = t, eng.decode_block_len
        key, *subs = jax.random.split(key, eng.decode_block_len + 1)
        r = eng.decode_block(
            params, cache, feed, np.asarray(subs), eos, budget,
            temp, top_k, top_p)
        cache, toks, counts = r.cache, r.tokens, r.counts
        toks = np.asarray(toks)
        for s in list(last_by_slot):
            got = [int(t) for t in toks[s, :int(np.asarray(counts)[s])]]
            streams[s].extend(got)
            last_by_slot[s] = got[-1]
    return cache, streams


def test_migration_resumes_bit_identical_and_conserves_refs(
        tiny_model_kwargs):
    """Seat a slot on shard 0 of a dp=2 paged engine, decode, migrate it
    to shard 1 through migrate_slot, keep decoding: the full stream must
    equal the never-migrated twin's, the freed source references must
    return to shard 0's pool, and the destination pages must be owed to
    exactly the migrated slot (refcount 1 each)."""
    prompt = [1, 2, 3, 4, 5, 6, 7]

    def run(migrate):
        cfg, eng = _engine(tiny_model_kwargs, 2, kv_layout="paged")
        params = _params(cfg, eng)
        cache = eng.init_cache()
        cache, first = _seat(eng, params, cache, 0, prompt)
        cache, pre = _decode_rounds(eng, params, cache, {0: first},
                                    rounds=1)
        last = pre[0][-1]
        slot = 0
        moved = 0
        if migrate:
            p = eng.paged
            live_before = sum(int(np.sum(r[1:] > 0))
                              for r in _refs_snapshot(p))
            cache, moved = eng.migrate_slot(cache, 0, 2,
                                            prompt_ids=prompt)
            slot = 2
            assert moved > 0
            # shard 1 now owes the slot its pages at refcount 1; the
            # radix re-graft may hold extra references on the prompt's
            # whole pages, so the slot's rows read >= 1
            refs = _refs_snapshot(p)
            npages = p.pages_for(int(p.host_len[2]))
            local = np.asarray(p.shards[1].tables)[p.local_slot(2),
                                                   :npages]
            assert all(refs[1][q] >= 1 for q in local)
            assert int(p.host_len[0]) == 0
            # page count is conserved: the move shifts live pages from
            # shard 0 to shard 1, it never mints or leaks them
            live_after = sum(int(np.sum(r[1:] > 0)) for r in refs)
            assert live_after == live_before
        cache, post = _decode_rounds(eng, params, cache, {slot: last},
                                     rounds=2)
        return pre[0] + post[slot]

    assert run(migrate=True) == run(migrate=False)


def test_migration_after_speculative_verify_exports_accepted_only(
        tiny_model_kwargs):
    """A verify round writes spec_len + 1 rows optimistically; rejected
    drafts strand past the length pointer. Migrating the slot right
    after must export ONLY the accepted prefix — the migrated stream
    equals the unmigrated twin's, drafts rolled back by construction."""
    prompt = [5, 9, 5, 9, 5, 9]

    def run(migrate):
        cfg, eng = _engine(tiny_model_kwargs, 2, kv_layout="paged",
                           spec_len=2)
        params = _params(cfg, eng)
        cache = eng.init_cache()
        cache, first = _seat(eng, params, cache, 0, prompt)
        n = eng.slots
        # one verify round with deliberately-poor drafts (repeat the last
        # token): some columns reject, leaving garbage rows in the pages
        toks = np.zeros((n, eng.spec_len + 1), np.int32)
        toks[0] = [first, first, first]
        budget = np.zeros(n, np.int32)
        budget[0] = 8
        r = eng.verify(
            params, cache, toks, jax.random.PRNGKey(1),
            np.full(n, -1, np.int32), budget, np.zeros(n, np.float32),
            np.zeros(n, np.int32), np.ones(n, np.float32))
        cache, emitted, counts = r.cache, r.tokens, r.counts
        got = [int(t) for t in
               np.asarray(emitted)[0, :int(np.asarray(counts)[0])]]
        slot = 0
        if migrate:
            cache, _ = eng.migrate_slot(cache, 0, 3, prompt_ids=prompt)
            slot = 3
        cache, post = _decode_rounds(eng, params, cache,
                                     {slot: got[-1]}, rounds=2)
        return got + post[slot]

    assert run(migrate=True) == run(migrate=False)


def test_migration_dest_pool_exhaustion_aborts_cleanly(tiny_model_kwargs):
    """Destination shard out of pages: the all-or-nothing allocation
    raises BEFORE anything moves — source slot untouched (length, table
    row), every shard's refcounts byte-identical to the pre-attempt
    snapshot."""
    cfg, eng = _engine(tiny_model_kwargs, 2, kv_layout="paged",
                       kv_page_len=8, kv_num_pages=12)  # 6/shard, 5 usable
    params = _params(cfg, eng)
    cache = eng.init_cache()
    p = eng.paged
    # shard 0: the would-be migrant (3 pages at page_len 8)
    cache, _ = _seat(eng, params, cache, 0, [1 + (i % 9) for i in range(17)])
    # shard 1: slot 2 pins 4 of the 5 usable pages
    cache, _ = _seat(eng, params, cache, 2,
                     [(2 * i) % 11 + 1 for i in range(25)])
    refs_before = _refs_snapshot(p)
    len_before = int(p.host_len[0])
    row_before = np.asarray(p.tables)[0].copy()
    with pytest.raises(paged_kv.PagePoolExhausted):
        eng.migrate_slot(cache, 0, 3)
    for got, want in zip(_refs_snapshot(p), refs_before):
        np.testing.assert_array_equal(got, want)
    assert int(p.host_len[0]) == len_before
    np.testing.assert_array_equal(np.asarray(p.tables)[0], row_before)


def test_migration_dead_peer_exits_77_without_page_leak(
        tiny_model_kwargs, tmp_path):
    """A dp peer whose ClusterMonitor lease went silent is discovered by
    the liveness check BETWEEN the page gather and the donating write:
    the migration exits through the monitor's exit path (the injected
    exit_fn stands in for os._exit(EXIT_CLUSTER_FAILED)) and the except
    arm releases every destination page — a restart finds both pools
    exactly as before the attempt."""
    cfg, eng = _engine(tiny_model_kwargs, 2, kv_layout="paged")
    params = _params(cfg, eng)
    cache = eng.init_cache()
    cache, _ = _seat(eng, params, cache, 0, [1, 2, 3, 4, 5, 6, 7, 8, 9])

    def exit_fn(peer, age):
        raise SystemExit(EXIT_CLUSTER_FAILED)

    m = ClusterMonitor(str(tmp_path), 0, 2, peer_timeout_s=5.0,
                       exit_fn=exit_fn)
    os.makedirs(m.dir, exist_ok=True)
    m._births = {1: time.time() - 60.0}
    with open(m.lease_path(1), "w") as f:
        f.write("3")
    old = time.time() - 30.0
    os.utime(m.lease_path(1), (old, old))
    assert m.check_peers() is not None  # the lease IS stale
    eng.attach_monitor(m)
    refs_before = _refs_snapshot(eng.paged)
    len_before = int(eng.paged.host_len[0])
    with pytest.raises(SystemExit) as ei:
        eng.migrate_slot(cache, 0, 2)
    assert ei.value.code == EXIT_CLUSTER_FAILED
    for got, want in zip(_refs_snapshot(eng.paged), refs_before):
        np.testing.assert_array_equal(got, want)
    assert int(eng.paged.host_len[0]) == len_before
    assert int(eng.paged.host_len[2]) == 0


# --------------------------------------------------------------------------- #
# batcher-level planner: the skewed workload migrates, streams stay exact
# --------------------------------------------------------------------------- #


def test_batcher_rebalance_fires_and_streams_stay_exact(tiny_model_kwargs):
    """The end-to-end planner path: long streams land on shard 0, shard
    1's short streams retire, the watermark trips, ONE slot migrates
    cross-shard mid-run — and
    every stream still equals the dp=1 baseline. The migration counters
    and per-shard occupancy gauges land in stats()/the registry."""
    base, _ = _run(tiny_model_kwargs, 1, "block", kv_layout="paged",
                   kv_page_len=8)
    got, b = _run(tiny_model_kwargs, 2, "block", kv_layout="paged",
                  kv_page_len=8)
    assert got == base
    st = b.stats()
    assert st["rebalance_count"] >= 1
    assert st["rebalance_bytes"] > 0
    assert st["slots_total"] == 4 and st["dp_size"] == 2
    assert len(st["shard_occupancy"]) == 2
    b.refresh_gauges()
    prom = b.obs.registry.prometheus()
    assert "picotron_dp_size 2" in prom
    assert 'picotron_shard_occupancy{shard="0"}' in prom
    assert 'picotron_shard_occupancy{shard="1"}' in prom
    assert ('picotron_slot_migrations_total{outcome="ok"}' in prom)


def test_dp1_default_unchanged(tiny_model_kwargs):
    """inference.dp_size defaults to 1 and the dp=1 engine reports the
    degenerate topology — one shard holding every slot, planner inert —
    while stats()/gauges still carry the (trivial) dp fields so scrapers
    see one schema."""
    cfg, eng = _engine(tiny_model_kwargs, 1, kv_layout="paged")
    assert cfg.inference.dp_size == 1
    assert eng.slots_per_shard == eng.slots
    b = ContinuousBatcher(eng, _params(cfg, eng))
    res = b.run([Request("r", [1, 2, 3], max_new_tokens=6)])
    assert res["r"].finish_reason == "length"
    st = b.stats()
    assert st["dp_size"] == 1
    assert st["shard_occupancy"] == [0]
    assert st["rebalance_count"] == 0
