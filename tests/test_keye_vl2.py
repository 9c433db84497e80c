"""The Keye-VL-2.0 block (models/keye_vl2.py) on the serving path, at toy size
in float32 on the CPU with seeded weights, against the plain reference
(benchmarks/reference/keye_vl2.py): the engine's programs through a cache of
two leaves (a token's K and V heads in one row, the indexer's keys two to a
row) with the toy ``topk``
well under the prompt, the decode step's gather of the chosen rows against
the masked walk, ``ops/select.py``'s row indices, M-RoPE over three streams,
q/k norm, the softmax router, the expert share, what the programs count, and
what ``Config.validate`` refuses."""

from functools import partial
import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import block_toys
from engine_memo import admit, decode, memoized, program_logits, worst_rel_err

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.inference import InferenceEngine, kv_cache
from picotron_tpu.models import STATS, experts, keye_vl2
from picotron_tpu.models import deepseek_v32 as dsv
from picotron_tpu.ops import rope, select

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "keye-vl-2.0-ep8-l12"
CELL = NAME + ".serve-longctx-decode"

# what is new is kept: K/V heads fewer than query heads (2 of 8), indexer
# keys of 64 (two to a cache row) on 4 heads, a topk (16) well under the
# prompt (70), three M-RoPE sections, a softmax top-4 of 16 with 4 held
TOY = block_toys.TOYS["KeyeVL2"]


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_keye_vl2",
        os.path.join(ROOT, "benchmarks", "reference", "keye_vl2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


make_config = partial(block_toys.make_config, "KeyeVL2", seq_length=256)


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, slots=2, max_seq_len=256,
                             **{"prefill_chunk": 32, **kw})
    params = jax.jit(lambda k: keye_vl2.init_params(k, cfg.model))(
        jax.random.PRNGKey(7))
    return cfg, engine, engine.shard_params(params)


def reference_rows(params, seq, n_prompt, model=TOY, **kw):
    return ref.forward_logits(params, np.asarray([seq], np.int32),
                              dict(model), jax.devices()[0],
                              **kw)[0][n_prompt - 1:]


RNG = np.random.default_rng(3)
PROMPT = [int(t) for t in RNG.integers(1, 512, 70)]
OTHER = [int(t) for t in RNG.integers(1, 512, 70)]


@pytest.fixture
def toy():
    return make_engine()


def one_layer(seed: int = 2, model=None):
    """(cfg, one layer's leaves) at toy size."""
    cfg = make_config(model)
    stack = jax.jit(lambda k: keye_vl2.init_params(k, cfg.model))(
        jax.random.PRNGKey(seed))["layers"]
    return cfg, {n: v[0] for n, v in stack.items()}


# ---- (a) the engine's programs against the reference ----------------------


@pytest.mark.parametrize("n_prompt,chunk,select_on", [
    (70, 32, True),    # chunks smaller than the prompt, prompt > topk:
                       # keys dropped in the chunks and in the gather
    (70, 256, True),   # the one-shot prefill program, then the gather
    (12, 32, False),   # under topk: every key kept, plain GQA
])
def test_prefill_and_decode_match_the_reference(n_prompt, chunk, select_on):
    _, engine, params = make_engine(prefill_chunk=chunk)
    prompt = PROMPT[:n_prompt]
    seq, got, _ = program_logits(engine, params, prompt)
    want = reference_rows(params, seq, n_prompt, select=select_on)
    assert worst_rel_err(got, want) < 1e-3
    # what the programs counted, in each of the three layers: every query
    # selects min(topk, t + 1) of the t + 1 keys it scored; a prefill's
    # queries attend under a mask over their context's rows, a decode step
    # reads the rows it chose and no other
    stats = dict(zip(keye_vl2.STAT_NAMES, engine.take_stats()))
    t = np.arange(n_prompt + 4)
    assert stats["dsa_keys_selected"] == 3 * np.minimum(16, t + 1).sum()
    assert stats["dsa_keys_scored"] == 3 * (t + 1).sum()
    assert stats["dsa_rows_attended"] == 3 * (
        (t[:n_prompt] + 1).sum() + np.minimum(16, t[n_prompt:] + 1).sum())


def test_selection_matters_past_topk():
    _, engine, params = make_engine()
    seq, got, _ = program_logits(engine, params, PROMPT)
    off = reference_rows(params, seq, len(PROMPT), select=False)
    assert worst_rel_err(got, off) > 0.1


def test_a_decode_block_counts_and_is_the_steps_one_by_one(toy):
    _, engine, params = toy
    cache, last = admit(engine, params, engine.init_cache(), PROMPT[:40])
    cache, _ = admit(engine, params, cache, OTHER[:22], slot=1)
    engine.take_stats()
    n = engine.decode_block_len
    keys = np.stack([np.asarray(jax.random.PRNGKey(i)) for i in range(n)])
    toks = np.asarray([int(np.argmax(last)), 5], np.int32)
    r = engine.decode_block(
        params, jax.tree.map(jnp.copy, cache), toks, keys,
        -np.ones(2, np.int32), np.full(2, n, np.int32),
        np.zeros(2, np.float32), np.zeros(2, np.int32),
        np.ones(2, np.float32))
    assert list(np.asarray(r.counts)) == [n, n]
    stats = dict(zip(keye_vl2.STAT_NAMES, engine.take_stats()))
    ctx = np.asarray([[40 + i + 1, 22 + i + 1] for i in range(n)])
    assert stats["dsa_rows_attended"] == stats["dsa_keys_selected"] \
        == 3 * np.minimum(16, ctx).sum()
    assert stats["dsa_keys_scored"] == 3 * ctx.sum()
    assert stats["moe_layer_steps"] == 3 * n
    assert stats["moe_pipelined_steps"] == 3 * n  # the toy experts fit
    # the block's tokens are the single steps' argmaxes
    seq0 = [int(toks[0])]
    for _ in range(n):
        cache, logits = decode(engine, params, cache, seq0[-1], slot=0)
        seq0.append(int(np.argmax(logits)))
    assert list(np.asarray(r.tokens)[0]) == seq0[1:]


def test_a_chunk_boundary_changes_nothing():
    _, a, params = make_engine(prefill_chunk=32)
    _, b, _ = make_engine(prefill_chunk=16)
    _, la = admit(a, params, a.init_cache(), PROMPT)
    _, lb = admit(b, params, b.init_cache(), PROMPT)
    assert worst_rel_err([la], [lb]) < 1e-5


def test_a_slot_used_twice_forgets_its_first_occupant(toy):
    _, engine, params = toy
    cache, _ = admit(engine, params, engine.init_cache(), OTHER)
    cache = engine.release(cache, 0)
    seq, got, _ = program_logits(engine, params, PROMPT[:50], cache=cache)
    assert worst_rel_err(got, reference_rows(params, seq, 50)) < 1e-3


# ---- (b) the gather against the masked walk, the selection ----------------


def _filled_cache(cfg, lp, S=96, B=2, seed=4):
    """A one-layer cache of ``B`` slots holding ``S`` rows each, written by
    the block's own chunk path, and the stream that wrote it."""
    m = cfg.model
    cache = keye_vl2.init_cache(m, B, 128)
    cos, sin = keye_vl2.serving_rope_tables(m, 128, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, S, m.hidden_size))
    leaves = {n: cache[n][:1] for n in keye_vl2.LEAVES}
    for b in range(B):
        _, out, *_ = keye_vl2.attention(
            lp, x[b:b + 1], cos[:S], sin[:S], m,
            {**leaves, "slot": jnp.int32(b)}, jnp.zeros((1,), jnp.int32), 0,
            jnp.ones((1, S), bool), False)
        leaves = {n: out[n] for n in keye_vl2.LEAVES}
    return leaves, x, (cos, sin)


def test_the_gathered_attend_is_the_masked_attend_on_the_same_cache():
    cfg, lp = one_layer()
    m = cfg.model
    src, _, _ = _filled_cache(cfg, lp)
    B, nh, hd = 2, m.num_attention_heads, m.head_dim
    q = jax.random.normal(jax.random.PRNGKey(9), (B, 1, nh, hd))
    pos_q = jnp.asarray([[95], [60]], jnp.int32)
    scores = jax.random.normal(jax.random.PRNGKey(10), (B, 1, 128))
    scores = jnp.where(jnp.arange(128)[None, None] <= pos_q[..., None],
                       scores, -jnp.inf)
    chosen = select.select_keys(scores, 16)
    rows, count = select.select_rows(scores, 16)
    walked = keye_vl2._attend_chosen(q, chosen, src, 0, hd ** -0.5, pos_q)
    gathered = keye_vl2._attend_rows(q, src, 0, rows[:, 0], count[:, 0],
                                     hd ** -0.5)
    np.testing.assert_allclose(gathered, walked, atol=2e-6)
    # the gather reads the chosen rows and no other: garbage everywhere else
    # changes nothing, garbage in a chosen row does
    keep = np.zeros((B, 128), bool)
    for b in range(B):
        keep[b, np.asarray(rows[b, 0, :int(count[b, 0])])] = True
    junk = jnp.where(keep[None, :, :, None, None], src["kv"], 1e4)
    again = keye_vl2._attend_rows(q, {**src, "kv": junk}, 0, rows[:, 0],
                                  count[:, 0], hd ** -0.5)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(gathered))
    hit = int(rows[0, 0, 3])
    moved = src["kv"].at[0, 0, hit, m.num_key_value_heads:].add(1.0)  # V
    again = keye_vl2._attend_rows(q, {**src, "kv": moved}, 0, rows[:, 0],
                                  count[:, 0], hd ** -0.5)
    assert np.abs(np.asarray(again[0] - gathered[0])).max() > 1e-3


def _scores_with(kind: str):
    """[2, 3, 300] float32 rows for ``select_rows``: ``ties`` (few distinct
    values, so that the threshold splits a run of equals), ``short`` (rows
    that see fewer than k keys, one that sees none but the first),
    ``plain`` (distinct values over an uneven number of keys)."""
    rng = np.random.default_rng(11)
    s = rng.standard_normal((2, 3, 300)).astype(np.float32)
    if kind == "ties":
        s = np.round(s * 2) / 2 + 0.0  # no -0.0: its bits sort below 0.0's
    seen = np.full((2, 3), 299)
    if kind == "short":
        seen = np.asarray([[0, 7, 39], [40, 41, 299]])
    s[np.arange(300)[None, None] > seen[..., None]] = -np.inf
    return s


@pytest.mark.parametrize("kind", ["plain", "ties", "short"])
@pytest.mark.parametrize("k", [40, 128, 512])
def test_select_rows_are_the_masks_set_rising_with_its_count(kind, k):
    s = _scores_with(kind)
    mask = np.asarray(select.select_keys(jnp.asarray(s), k))
    rows, count = (np.asarray(a) for a in
                   jax.jit(lambda s: select.select_rows(s, k))(s))
    assert rows.shape == (2, 3, min(k, 300)) and rows.dtype == np.int32
    for b in range(2):
        for q in range(3):
            want = np.flatnonzero(mask[b, q])
            n = int(count[b, q])
            assert n == len(want) == min(k, int(np.isfinite(s[b, q]).sum()))
            np.testing.assert_array_equal(rows[b, q, :n], want)
            assert (np.diff(rows[b, q, :n]) > 0).all()
            assert (rows[b, q, n:] == 0).all()
            # ties go to the lower index: no key outside the set scores as
            # high as the set's lowest unless it lies past it
            if 0 < n < np.isfinite(s[b, q]).sum():
                low = s[b, q, want].min()
                out = np.setdiff1d(np.flatnonzero(np.isfinite(s[b, q])),
                                   want)
                assert (s[b, q, out] <= low).all()
                eq = out[s[b, q, out] == low]
                assert (eq > want[s[b, q, want] == low].max()).all()


def test_chosen_rows_of_a_mask_that_fills_whole_blocks():
    mask = np.zeros((1, 1, 1024), bool)
    mask[0, 0, 128:384] = True  # two whole blocks of 128
    mask[0, 0, 1023] = True
    rows, count = select.chosen_rows(jnp.asarray(mask), 300)
    assert int(count[0, 0]) == 257
    np.testing.assert_array_equal(
        np.asarray(rows[0, 0, :257]), np.r_[128:384, 1023])


def test_the_output_ignores_a_key_it_did_not_choose(monkeypatch):
    """A decode step's attention: the value of a live key the indexer did
    not choose moves nothing; that of one it chose does."""
    cfg, lp = one_layer()
    m = cfg.model
    src, _, (cos, sin) = _filled_cache(cfg, lp)
    seen = {}
    real = select.select_rows

    def spying(scores, k):
        rows, count = real(scores, k)  # [1, slots, k], [1, slots]
        seen["rows"], seen["count"] = rows[0], count[0]
        return rows, count

    monkeypatch.setattr(select, "select_rows", spying)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 1, m.hidden_size))
    pos = jnp.asarray([96, 96], jnp.int32)

    def step(src):
        return keye_vl2.attention(
            lp, x, cos[96][None, None], sin[96][None, None], m, dict(src),
            pos, 0, jnp.ones((2, 1), bool), False)[0]

    base = step(src)
    rows = np.asarray(seen["rows"][0, :int(seen["count"][0])])
    assert len(rows) == 16
    dropped = next(t for t in range(96) if t not in rows)
    out = step({**src, "kv": src["kv"].at[0, 0, dropped].add(3.0)})
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))
    out = step({**src, "kv": src["kv"].at[0, 0, int(rows[5])].add(3.0)})
    assert np.abs(np.asarray(out[0] - base[0])).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(base[1]))


def test_index_scores_over_keys_two_to_a_row_are_those_of_a_key_a_row():
    rng = np.random.default_rng(13)
    B, S, T, h, D = 2, 3, 4096, 4, 64  # past KEY_BLOCK: the walk's loop
    qi = jnp.asarray(rng.standard_normal((B, S, h, D)), jnp.float32)
    wi = jnp.asarray(rng.standard_normal((B, S, h)), jnp.float32)
    ki = jnp.asarray(rng.standard_normal((1, B, T, D)), jnp.float32)
    pos_q = jnp.asarray([[5, 2100, 4095], [0, 2047, 2048]], jnp.int32)
    plain = select.index_scores(qi, wi, {"ki": ki}, 0, pos_q)
    packed = select.index_scores(
        qi, wi, {"ki": ki.reshape(1, B, T // 2, 2 * D)}, 0, pos_q)
    np.testing.assert_allclose(packed, plain, rtol=1e-5, atol=1e-5)
    assert np.isinf(np.asarray(packed)[0, 0, 6:]).all()
    # causal: a key past the query changes nothing
    moved = ki.at[0, 0, 6].add(5.0).reshape(1, B, T // 2, 2 * D)
    again = select.index_scores(qi, wi, {"ki": moved}, 0, pos_q)
    np.testing.assert_array_equal(np.asarray(again[0, 0]),
                                  np.asarray(packed[0, 0]))
    assert dsv.index_scores is select.index_scores  # shared, not copied


@pytest.mark.parametrize("start,n", [(0, 8), (5, 8), (120, 8), (7, 1),
                                     (3, 5)])
def test_a_block_of_keys_lands_whichever_place_it_starts_at(start, n):
    cfg, _ = one_layer()
    leaf = jnp.arange(2 * 2 * 64 * 128, dtype=jnp.float32).reshape(
        2, 2, 64, 128)
    ki = -jnp.ones((1, n, 64)) * jnp.arange(1, n + 1)[None, :, None]
    got = keye_vl2.write_keys({"ki": leaf, "slot": jnp.int32(1)}, ki,
                              jnp.asarray([start]), 1)
    flat = np.asarray(leaf).reshape(2, 2, 128, 64).copy()
    flat[1, 1, start:start + n] = np.asarray(ki[0])
    np.testing.assert_array_equal(np.asarray(got).reshape(2, 2, 128, 64),
                                  flat)


def test_a_decode_step_writes_one_key_a_slot_into_its_half_row():
    leaf = jnp.zeros((2, 3, 8, 128))
    ki = jnp.stack([jnp.full((1, 64), v) for v in (1.0, 2.0, 3.0)])
    got = np.asarray(keye_vl2.write_keys(
        {"ki": leaf}, ki, jnp.asarray([4, 7, 0]), 1)).reshape(2, 3, 16, 64)
    want = np.zeros((2, 3, 16, 64), np.float32)
    want[1, 0, 4], want[1, 1, 7], want[1, 2, 0] = 1.0, 2.0, 3.0
    np.testing.assert_array_equal(got, want)


# ---- (c) M-RoPE, q/k norm, the router ---------------------------------------


def test_mrope_with_equal_streams_is_apply_rope_on_the_plain_table():
    cos, sin = rope.precompute_rope(64, 32, 10000.0, jnp.float32)
    pos = jnp.asarray([[3, 9, 40], [0, 1, 63]], jnp.int32)
    plain = rope.rope_at_positions(cos, sin, pos)
    three = rope.mrope_at_positions(cos, sin, jnp.stack([pos] * 3),
                                    [4, 6, 6])
    for a, b in zip(three, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 4, 32))
    np.testing.assert_array_equal(
        np.asarray(rope.apply_rope(x, *three)),
        np.asarray(rope.apply_rope(x, *plain)))
    with pytest.raises(ValueError, match="mrope_section"):
        rope.mrope_at_positions(cos, sin, jnp.stack([pos] * 3), [4, 6, 5])


def test_mrope_takes_each_pair_from_the_stream_that_owns_it():
    cos, sin = rope.precompute_rope(64, 32, 10000.0, jnp.float32)
    pos = jnp.asarray([[[7]], [[20]], [[33]]], jnp.int32)  # t, h, w
    c, s = rope.mrope_at_positions(cos, sin, pos, [4, 6, 6])
    owner = np.tile(np.repeat([7, 20, 33], [4, 6, 6]), 2)
    np.testing.assert_array_equal(np.asarray(c[0, 0]),
                                  np.asarray(cos)[owner, np.arange(32)])
    np.testing.assert_array_equal(np.asarray(s[0, 0]),
                                  np.asarray(sin)[owner, np.arange(32)])


def whole_forward(params, cfg, tokens, positions):
    """The block with no cache on ``tokens`` [B, S] at three position
    streams [3, B, S]: the layer functions as the engine's one-shot prefill
    calls them, handed each stream's angle rows."""
    m = cfg.model
    cos, sin = keye_vl2.serving_rope_tables(m, 256, jnp.float32)
    n, B, S = positions.shape
    c3, s3 = (t.reshape(n, B, S, -1) for t in rope.rope_at_positions(
        cos, sin, positions.reshape(n * B, S)))
    h = params["embed"][tokens]  # one chip: the vocabulary is not sharded
    stack = params["layers"]
    whole = {k: stack[k] for k in keye_vl2.UNSLICED}
    for i in range(m.num_hidden_layers):
        lp = {k: v[i] for k, v in stack.items() if k not in whole}
        h, _ = keye_vl2.decoder_layer({**lp, **whole, "row": i}, h, c3, s3,
                                      cfg)
    return keye_vl2.head_logits(params, h, cfg)


def test_three_unequal_streams_match_the_reference(toy):
    cfg, _, params = toy
    tokens = np.asarray([PROMPT[:48], OTHER[:48]], np.int32)
    rng = np.random.default_rng(5)
    # an image's span: the temporal position stands, height and width walk
    t = np.r_[np.arange(10), np.full(28, 10), np.arange(11, 21)]
    positions = np.stack([np.stack([t, t + rng.integers(0, 7, 48),
                                    t + rng.integers(0, 7, 48)], 0)
                          for _ in range(2)], 1)
    got = whole_forward(params, cfg, jnp.asarray(tokens),
                        jnp.asarray(positions, jnp.int32))
    want = ref.forward_logits(params, tokens, dict(TOY), jax.devices()[0],
                              positions=positions)
    assert worst_rel_err(list(np.asarray(got)), list(want)) < 1e-3
    # the streams matter: the same tokens as text read otherwise
    text = ref.forward_logits(params, tokens, dict(TOY), jax.devices()[0])
    assert worst_rel_err(list(np.asarray(got)), list(text)) > 1e-2
    # and with equal streams the block is the engine's own
    equal = np.broadcast_to(np.arange(48), (3, 2, 48))
    got = whole_forward(params, cfg, jnp.asarray(tokens),
                        jnp.asarray(equal, jnp.int32))
    assert worst_rel_err(list(np.asarray(got)), list(text)) < 1e-3


@pytest.mark.parametrize("change,floor", [
    ({"_without": ("qk_norm",)}, 1e-2),
    ({"_without": ("softmax_router",)}, 3e-3),
    ({"norm_topk_prob": False}, 3e-3),
])
def test_each_part_is_held_by_a_case_that_fails_without_it(change, floor,
                                                           toy):
    """The program against the reference with one part left out: q/k norm a
    head, the softmax over the router's width, the chosen experts' weights
    renormalised."""
    _, engine, params = toy
    seq, got, _ = program_logits(engine, params, PROMPT[:40])
    assert worst_rel_err(got, reference_rows(params, seq, 40)) < 1e-3
    faulty = reference_rows(params, seq, 40, dict(TOY, **change))
    assert worst_rel_err(got, faulty) > floor


def test_the_router_is_a_softmax_top_k_renormalised():
    cfg, lp = one_layer()
    m = cfg.model
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 9, m.hidden_size))
    seen = {}
    real = experts.held_weights

    def spying(chosen, weights, first, count):
        seen["chosen"], seen["weights"] = chosen, weights
        return real(chosen, weights, first, count)

    experts.held_weights, was = spying, experts.held_weights
    try:
        keye_vl2.expert_mlp(lp, x, m, jnp.ones((1, 9), bool))
    finally:
        experts.held_weights = was
    logits = np.asarray(x[0], np.float64) @ np.asarray(lp["router"],
                                                       np.float64)
    s = np.exp(logits - logits.max(-1, keepdims=True))
    s /= s.sum(-1, keepdims=True)
    order = np.argsort(-s, axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(np.asarray(seen["chosen"]), order)
    w = np.take_along_axis(s, order, -1)
    np.testing.assert_allclose(np.asarray(seen["weights"]),
                               w / w.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(seen["weights"]).sum(-1), 1.0,
                               rtol=1e-6)


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Ranks 0-3 of 4, four experts each, and no shared expert to count
    once, against the uncut layer of sixteen, and against the
    reference's."""
    uncut_model = dict(num_experts=16, ep_size=1)
    cfg, lp = one_layer(5, uncut_model)
    uncut = cfg.model
    assert "ws_gate" not in lp
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 128), jnp.float32)
    live = jnp.ones((2, 12), bool)

    def mlp(lp, x, m, live):
        return jax.jit(lambda lp, x, live: keye_vl2.expert_mlp(
            lp, x, m, live))(lp, x, live)

    whole, (assigned, hit, *_) = mlp(lp, x, uncut, live)
    assert int(assigned) == 2 * 12 * 4 and int(hit) <= 16
    total, held = jnp.zeros_like(whole), 0
    for rank in range(4):
        m = make_config(dict(ep_rank=rank)).model
        part = {**lp, **{n: lp[n][4 * rank:4 * rank + 4]
                         for n in ("w1", "w3", "w2")}}
        y, (n, *_) = mlp(part, x, m, live)
        total, held = total + y, held + int(n)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert held == 2 * 12 * 4  # every token's experts are held by some rank
    want = ref.experts(lp, x.reshape(24, 128), dict(TOY, **uncut_model))
    np.testing.assert_allclose(whole.reshape(24, 128), want, atol=2e-5)
    # rows that are not live are routed nowhere: nothing at all
    y, (n, *_) = mlp(lp, x, uncut, jnp.zeros((2, 12), bool))
    assert not np.asarray(y).any() and int(n) == 0


# ---- (d) the configuration, the cache, the counts ----------------------------


def published_config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def published_model() -> ModelConfig:
    sys.path.insert(0, ROOT)
    from benchmarks import common

    fields = {f.name for f in ModelConfig.__dataclass_fields__.values()}
    section = common.model_section(published_config())
    return ModelConfig(**{k: v for k, v in section.items() if k in fields})


def test_the_cache_holds_two_leaves_at_the_cells_sizes():
    """8 slots x 49,152: a token's four K heads and four V heads in one row
    of 2 KB; the indexer's keys two to a row of whole lanes: 9.66 + 0.60 =
    10.27 GB (a key a row of 64 would lie padded to 128: +0.60)."""
    sys.path.insert(0, ROOT)
    from benchmarks import opcount_keye as ok

    m = published_model()
    shapes = jax.eval_shape(lambda: keye_vl2.init_cache(m, 8, 49152))
    assert shapes["kv"].shape == (12, 8, 49152, 8, 128)
    assert shapes["ki"].shape == (12, 8, 24576, 128)
    assert all(shapes[n].dtype == jnp.bfloat16 for n in keye_vl2.LEAVES)
    assert keye_vl2.ki_pack(m) == 2
    pub = published_config()
    assert ok.cache_bytes(pub, 8, 49152) == (9_663_676_416, 603_979_776)
    assert kv_cache.cache_bytes(shapes) == 9_663_676_416 + 603_979_776 + 32
    assert round(kv_cache.cache_bytes(shapes) / 1e9, 2) == 10.27
    assert (ok.kv_bytes_per_row(pub), ok.index_key_bytes(pub)) == (2048, 128)
    assert set(keye_vl2.cache_pspecs(m)) == set(shapes)
    with pytest.raises(ValueError, match="multiple of 2"):
        keye_vl2.init_cache(m, 1, 1001)


def test_parameters_are_the_opcounts():
    sys.path.insert(0, ROOT)
    from benchmarks import opcount_keye as ok

    pub = published_config()
    assert keye_vl2.num_params(published_model()) == ok.num_params(pub) \
        == 1_240_586_752
    assert round(2 * ok.num_params(pub) / 1e9, 2) == 2.48
    assert ok.layer_params(pub) == 96_899_456
    p = ok.params_by_part(pub)
    assert (p["attention"], p["indexer"], p["routed_expert"]) \
        == (18_874_624, 2_261_120, 4_718_592)
    assert p["embed"] + p["head"] + p["final_norm"] == 77_793_280
    toy_m = make_config().model
    tree = jax.eval_shape(lambda: keye_vl2.init_params(jax.random.PRNGKey(0),
                                                       toy_m))
    assert keye_vl2.num_params(toy_m) == sum(
        v.size for v in jax.tree.leaves(tree)) == ok.num_params(
            dict(TOY, torch_dtype="float32"))
    # a step of slots at 100 and 30,000: the weights but the embedding, the
    # indexer's key of every live token, 100 + 2,048 chosen rows, a layer
    got = ok.decode_step_bytes(pub, [100, 30000])
    assert got == 2 * (1_240_586_752 - 18992 * 2048) \
        + 12 * (30100 * 128 + 2148 * 2048)
    assert ok.chosen_rows(pub, [100, 30000]) == 2148


def test_the_published_keys_reach_the_program_under_their_names():
    m = published_model()
    assert m.model_type == "KeyeVL2"
    assert (m.first_layer, m.total_layers) == (12, 48)
    assert (m.head_dim, m.num_attention_heads, m.num_key_value_heads) \
        == (128, 32, 4)
    assert keye_vl2.indexer(m) == (16, 64, 2048)
    assert (m.num_experts, m.num_local_experts, m.ep_size) == (16, 128, 8)
    assert keye_vl2.router_width(m) == 128 and m.num_experts_per_tok == 8
    assert m.rope_scaling["mrope_section"] == [16, 24, 24]
    assert m.rope_theta == 1e7 and m.norm_topk_prob is True
    assert (m.decoder_sparse_step, m.mlp_only_layers) == (1, [])
    Config.from_dict({"model": dict(vars(m), _head_dim=None,
                                    head_dim=128)}).validate()
    assert keye_vl2.layer_groups(m) == [("layers", keye_vl2.decoder_layer,
                                         12)]
    assert experts.takes_pipelined(8, 2048, 768, 2)  # the second such block


def test_seeded_draws_are_as_the_configuration_file_says(toy):
    _, _, params = toy
    layers = params["layers"]
    for name, gain in (("wq", 1.0), ("wo", keye_vl2.INIT_GAIN["wo"]),
                       ("w2", keye_vl2.INIT_GAIN["w2"]), ("router", 1.0)):
        w = np.asarray(layers[name], np.float32)
        bound = gain * (1.0 / w.shape[-2]) ** 0.5
        assert 0.9 * bound < np.abs(w).max() <= bound, name
    assert np.abs(np.asarray(layers["ki_bias"])).max() <= keye_vl2.KI_BIAS
    assert np.asarray(layers["ki_bias"]).std() > 0.03
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm", "ki_norm"):
        assert (np.asarray(layers[name]) == 1).all(), name
    assert "router_bias" not in layers and "ws_gate" not in layers
    assert layers["q_norm"].shape == layers["k_norm"].shape == (3, 32)


def test_training_is_refused_by_name():
    cfg = make_config()
    with pytest.raises(ValueError, match="KeyeVL2.*served, not trained"):
        cfg.validate(for_training=True)


@pytest.mark.parametrize("model,match", [
    ({"sa_config": None}, "needs model.sa_config"),
    ({"sa_config": dict(TOY["sa_config"], topk=0)}, "needs model.sa_config"),
    ({"sa_config": dict(TOY["sa_config"], indexer_num_kv_heads=2)},
     "indexer_num_kv_heads 1"),
    ({"rope_scaling": None}, "mrope_section"),
    ({"rope_scaling": {"mrope_section": [4, 6, 5]}}, "sum to head_dim / 2"),
    ({"rope_scaling": {"rope_type": "yarn", "mrope_section": [4, 6, 6]}},
     "rope_scaling of type 'default'"),
    ({"num_experts": 0}, "num_experts >= 1"),
    ({"ep_rank": 4}, "ep_rank 4 outside"),
    ({"num_experts_per_tok": 17}, "passes the router's width 16"),
    ({"num_local_experts": 4}, "is not the router's width 16"),
    ({"norm_topk_prob": False}, "norm_topk_prob = True only"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step = 1 only"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers = \\[\\] only"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings = False only"),
    ({"first_layer": 10, "total_layers": 12}, "lie outside total_layers 12"),
])
def test_validate_refuses_what_the_block_lacks(model, match):
    with pytest.raises(ValueError, match="KeyeVL2.*" + match):
        make_config(model)


def test_engine_keywords_are_refused_too():
    cfg = make_config()
    with pytest.raises(ValueError, match="KeyeVL2.*speculation"):
        InferenceEngine(cfg, slots=2, max_seq_len=128, spec_len=2)
    with pytest.raises(ValueError, match="KeyeVL2.*kv_layout 'paged'"):
        InferenceEngine(cfg, slots=2, max_seq_len=128, kv_layout="paged")


# ---- (e) the controls, the counters -----------------------------------------


def test_bfloat16_fails_the_float32_check(toy):
    _, engine, params = toy
    want = reference_rows(params, PROMPT, len(PROMPT))
    _, last = admit(engine, params, engine.init_cache(), PROMPT)
    assert worst_rel_err([last], want) < 1e-3
    _, low, _ = make_engine({"dtype": "bfloat16"})
    low_params = low.shard_params(jax.tree.map(
        lambda v: v.astype(jnp.bfloat16), params))
    _, last = admit(low, low_params, low.init_cache(), PROMPT)
    assert worst_rel_err([last], want) > 1e-3


@pytest.mark.parametrize("fault", ["selection_ignored", "topk_halved",
                                   "gather_off_by_one"])
def test_a_fault_in_the_selection_fails_the_check(fault, toy):
    """The three controls the cell's ``correct`` must see, at toy size, in
    the logits of a chunked prefill and four decode steps
    (benchmarks/tests/control_keye.py runs them and eight more on the
    chip)."""
    sys.path.insert(0, ROOT)
    from benchmarks.tests import control_keye

    _, sound, params = toy
    seq, got, _ = program_logits(sound, params, PROMPT)
    want = reference_rows(params, seq, len(PROMPT))
    assert worst_rel_err(got, want) < 1e-3
    kept = (keye_vl2.indexer, keye_vl2.gather_rows)
    with control_keye.fault(fault):
        _, engine, _ = make_engine(fresh=True)  # traced under the fault
        cache, last = admit(engine, params, engine.init_cache(), PROMPT)
        faulty = [last]
        for tok in seq[len(PROMPT):]:
            cache, logits = decode(engine, params, cache, tok)
            faulty.append(logits)
    decode_only = fault == "gather_off_by_one"
    assert worst_rel_err(faulty[decode_only:], want[decode_only:]) > 1e-2
    assert (keye_vl2.indexer, keye_vl2.gather_rows) == kept  # taken away


def test_the_batcher_puts_the_counters_on_metrics(toy):
    from picotron_tpu.inference import ContinuousBatcher, Request

    _, shared, params = toy
    _, engine, _ = make_engine(fresh=True)  # its registry's totals are read
    batcher = ContinuousBatcher(engine, params, seed=0)
    reqs = [Request(uid=f"r{i}", prompt=p, max_new_tokens=5)
            for i, p in enumerate((PROMPT[:44], OTHER[:6], OTHER[:20]))]
    out = batcher.run(reqs)
    assert all(len(out[r.uid].tokens) == 5 for r in reqs)
    text = engine.obs.registry.prometheus()
    got = {}
    for name in keye_vl2.STAT_NAMES:
        line, = [l for l in text.splitlines()
                 if l.startswith(f"picotron_{name}_total ")]
        got[name] = float(line.split()[1])

    def rows(n, cap, prompt_cap):
        # a prompt's queries, then its four decode steps (the fifth token
        # is sampled from the fourth step's logits)
        return sum(min(t + 1, prompt_cap) for t in range(n)) \
            + sum(min(t + 1, cap) for t in range(n, n + 4))

    big = 10 ** 6
    assert got["dsa_keys_scored"] == 3 * sum(
        rows(n, big, big) for n in (44, 6, 20))
    assert got["dsa_keys_selected"] == 3 * sum(
        rows(n, 16, 16) for n in (44, 6, 20))
    # a prefill's queries read their context under the mask, a decode
    # step's the rows it chose
    assert got["dsa_rows_attended"] == 3 * sum(
        rows(n, 16, big) for n in (44, 6, 20))
    assert got["moe_layer_steps"] > 0 and got["moe_assignments"] > 0
    alone = ContinuousBatcher(shared, params, seed=0).run(
        [Request(uid="x", prompt=OTHER[:20], max_new_tokens=5)])
    assert out["r2"].tokens == alone["x"].tokens


def test_stats_leave_the_programs_a_row_a_layer(toy):
    cfg, engine, params = toy
    h = jnp.zeros((1, 4, cfg.model.hidden_size))
    cos, sin = keye_vl2.serving_rope_tables(cfg.model, 8, jnp.float32)
    lp = {n: (v if n in keye_vl2.UNSLICED else v[0])
          for n, v in params["layers"].items()}
    _, out = keye_vl2.decoder_layer({**lp, "row": 0}, h, cos[:4], sin[:4],
                                    cfg, return_kv=True)
    assert out[STATS].shape == (len(keye_vl2.STAT_NAMES),)
    assert out[STATS].dtype == jnp.int32
    assert set(out) == set(keye_vl2.LEAVES) | {STATS}
    assert out["ki"].shape == (1, 2, 128)  # four keys of 64, two to a row
    assert out["kv"].shape == (1, 4, 4, 32)  # two K heads, then two V heads


# ---- (f) the cell ------------------------------------------------------------


def test_rehearsal_of_the_cell_computes_its_readers():
    """The cell's control flow at toy size on the CPU, led in and measured
    for 4 s each (as PR 39 steadied its twin against six workers)."""
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "4", "--trace", "2", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    # the device-trace readers need a chip
    assert {"serve_out_tokens_per_s", "serve_itl_p99_ms", "setup_s",
            "dsa.selected_pct", "dsa.attended_rows_pct.keye",
            "moe.held_assignments_per_step.keye"} <= set(out["computed"])


def test_a_program_without_the_block_fails_the_cell_at_once():
    """What the parent does with the new cell: ``sa_config``, the first of
    ``model_keys``, is a name its ``ModelConfig`` lacks, and the run ends
    with exit code 2 before any device work (here: a configuration that
    lists one more)."""
    sys.path.insert(0, ROOT)
    from benchmarks import common

    config = published_config()
    assert config["model_keys"][0] == "sa_config"
    m = common.model_section(config)
    assert m["model_type"] == "KeyeVL2" and m["head_dim"] == 128
    assert m["sa_config"]["topk"] == 2048 and m["num_experts"] == 16
    assert common.load_reference(config).__file__.endswith("keye_vl2.py")
    config["model_keys"] = ["tower_mystery"] + config["model_keys"]
    config["tower_mystery"] = 1
    with pytest.raises(SystemExit) as e:
        common.model_section(config)
    assert e.value.code == 2


def test_the_configuration_keeps_every_published_number():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    config = published_config()
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size", "ep_size"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["reduced_from"]["num_experts"] == row["config"][
        "num_experts"] == config["num_experts"] * config["ep_size"] \
        == config["num_local_experts"]
    assert row["config"]["vocab_size"] == 8 * config["vocab_size"]
    assert row["config"]["num_hidden_layers"] == 4 * config[
        "num_hidden_layers"] == config["total_layers"]
    assert any("vision tower" in d for d in config["departures"])
    assert any("FP8" in d for d in config["departures"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["reduced"] == config["reduced"]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert (cell["traffic"], cell["chips"]) == ("longctx-decode-closed-48k",
                                                1)
    assert len(manifest["workloads"]) >= 11
