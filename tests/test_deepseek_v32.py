"""The DeepSeek-V3.2 block (models/deepseek_v32.py) on the serving path, at toy
size in float32 on the CPU with seeded weights, against the plain reference
(benchmarks/reference/deepseek_v32.py): the engine's programs through the
latent cache, the expert share, the router and the selection by hand, the
YaRN tables, what ``Config.validate`` refuses, and the bfloat16 control."""

from functools import partial
import importlib.util
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import block_toys
from engine_memo import admit, memoized

from picotron_tpu.inference import InferenceEngine
from picotron_tpu.models import deepseek_v32 as dsv
from picotron_tpu.models import experts
from picotron_tpu.ops import rope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "deepseek-v3.2-ep32-l7.serve-longctx-decode"

YARN = block_toys.YARN
TOY = block_toys.TOYS["deepseek_v32"]


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_deepseek_v32",
        os.path.join(ROOT, "benchmarks", "reference", "deepseek_v32.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


make_config = partial(block_toys.make_config, "deepseek_v32", seq_length=256)


def ref_config(model: dict) -> dict:
    """The reference takes the published keys, as a configuration file
    gives them."""
    return dict(model, torch_dtype=model["dtype"])


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, slots=2, max_seq_len=256,
                             **{"prefill_chunk": 32, **kw})
    params = jax.jit(lambda k: dsv.init_params(k, cfg.model))(
        jax.random.PRNGKey(7))
    return cfg, engine, engine.shard_params(params)


def program_logits(engine, params, prompt, steps=4, follow=None):
    """The runner's check (benchmarks/runners/serve.py::program_logits):
    the prompt through prefill (in chunks past ``prefill_chunk``), then
    ``steps`` decode steps through the cache; (sequence, logits rows)."""
    seq = list(prompt)
    if len(prompt) > engine.prefill_chunk:
        cache, last = engine.prefill_chunked(params, engine.init_cache(),
                                             prompt, 0)
    else:
        kv, last = engine.prefill(params, prompt)
        cache = engine.insert(engine.init_cache(), kv, 0, len(prompt))
    got = [np.asarray(last, np.float32)[0]]
    for i in range(steps):
        seq.append(int(follow[i]) if follow is not None
                   else int(np.argmax(got[-1])))
        toks = np.zeros(engine.slots, np.int32)
        toks[0] = seq[-1]
        cache, _, logits = engine.decode_step(
            params, cache, toks, jax.random.PRNGKey(0),
            np.zeros(engine.slots, np.float32),
            np.zeros(engine.slots, np.int32),
            np.ones(engine.slots, np.float32))
        got.append(np.asarray(logits, np.float32)[0])
    return seq, got


def worst_rel_err(got, want) -> float:
    return max(float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
               for g, w in zip(got, want))


def reference_rows(params, seq, n_prompt, model, select=True):
    return ref.forward_logits(params, np.asarray([seq], np.int32),
                              ref_config(model), jax.devices()[0],
                              select=select)[0][n_prompt - 1:]


PROMPT = [int(t) for t in np.random.default_rng(3).integers(1, 512, 70)]


# ---- (a) the engine's programs against the reference ----------------------


@pytest.mark.parametrize("n_prompt,chunk,select", [
    (70, 32, True),    # chunks smaller than the prompt, prompt > index_topk
    (70, 256, True),   # the one-shot prefill program
    (12, 32, False),   # shorter than index_topk: plain MLA, selection off
])
def test_prefill_and_decode_match_the_reference(n_prompt, chunk, select):
    cfg, engine, params = make_engine(prefill_chunk=chunk)
    prompt = PROMPT[:n_prompt]
    seq, got = program_logits(engine, params, prompt)
    want = reference_rows(params, seq, n_prompt, TOY, select=select)
    assert worst_rel_err(got, want) < 1e-3
    # what the programs counted: every query selects min(topk, t + 1) of
    # the t + 1 keys it scored, in each of the three layers; a prefill's
    # queries attend under a mask over their context's rows, a decode step
    # reads the rows it chose and no other (none takes the walk)
    stats = dict(zip(dsv.STAT_NAMES, engine.take_stats()))
    t = np.arange(n_prompt + 4)
    assert stats["dsa_keys_selected"] == 3 * np.minimum(16, t + 1).sum()
    assert stats["dsa_keys_scored"] == 3 * (t + 1).sum()
    assert stats["dsa_rows_attended"] == 3 * (
        (t[:n_prompt] + 1).sum() + np.minimum(16, t[n_prompt:] + 1).sum())


def test_selection_matters_past_index_topk():
    """With the selection switched off the reference is another model once
    the context passes ``index_topk``: the check can tell."""
    cfg, engine, params = make_engine()
    seq, got = program_logits(engine, params, PROMPT)
    dense = reference_rows(params, seq, len(PROMPT), TOY, select=False)
    assert worst_rel_err(got, dense) > 1e-2


def test_decode_block_counts_and_matches_single_steps():
    cfg, engine, params = make_engine()
    seq, got = program_logits(engine, params, PROMPT, steps=3)
    engine.take_stats()
    cache, last = engine.prefill_chunked(params, engine.init_cache(),
                                         PROMPT, 0)
    engine.take_stats()
    keys = np.stack([np.asarray(jax.random.PRNGKey(i))
                     for i in range(engine.decode_block_len)])
    toks = np.zeros(2, np.int32)
    toks[0] = seq[len(PROMPT)]
    r = engine.decode_block(
        params, cache, toks, keys, -np.ones(2, np.int32),
        np.array([2, 0], np.int32), np.zeros(2, np.float32),
        np.zeros(2, np.int32), np.ones(2, np.float32))
    cache, out, counts = r.cache, r.tokens, r.counts
    assert list(np.asarray(counts)) == [2, 0]
    assert list(np.asarray(out)[0, :2]) == seq[len(PROMPT) + 1:]
    stats = dict(zip(dsv.STAT_NAMES, engine.take_stats()))
    # 8 steps x 2 expert layers (the loop: both slots' rows through both
    # held experts); the parked slot is scored at every step (the free one
    # never), and selects index_topk of its keys
    assert stats["moe_layer_steps"] == 16
    assert stats["moe_expert_rows"] == 16 * 2 * 2
    assert stats["dsa_rows_attended"] == stats["dsa_keys_selected"] \
        == 8 * 3 * 16


def test_a_decode_step_with_contexts_on_both_sides_of_index_topk():
    """One slot at 12 tokens (under ``index_topk`` 16: every key kept, the
    gathered rows past its count are row 0 again and must weigh nothing),
    one at 70 (16 of its keys), decoded side by side."""
    _, engine, params = make_engine()
    short, long = PROMPT[:12], PROMPT[5:] + PROMPT[:5]
    cache, last_s = admit(engine, params, engine.init_cache(), short, slot=0)
    cache, last_l = admit(engine, params, cache, long, slot=1)
    seqs = [short + [int(np.argmax(last_s))], long + [int(np.argmax(last_l))]]
    got = [[], []]
    for _ in range(3):
        toks = np.asarray([seq[-1] for seq in seqs], np.int32)
        cache, _, logits = engine.decode_step(
            params, cache, toks, jax.random.PRNGKey(0),
            np.zeros(2, np.float32), np.zeros(2, np.int32),
            np.ones(2, np.float32))
        for b, row in enumerate(np.asarray(logits, np.float32)):
            got[b].append(row)
            seqs[b].append(int(np.argmax(row)))
    for b, n in enumerate((12, 70)):
        want = reference_rows(params, seqs[b][:-1], n + 1, TOY)
        assert worst_rel_err(got[b], want) < 1e-3, b
    engine.take_stats()


def _one_layer_cache(S=40, B=2):
    """(layer parameters, a one-layer latent cache of ``B`` slots holding
    ``S`` rows each, written by the block's own chunk path, the tables)."""
    m = make_config().model
    params = jax.jit(lambda k: dsv.init_params(k, m))(jax.random.PRNGKey(2))
    lp = jax.tree.map(lambda v: v[0], params["layers"])
    cache = dsv.init_cache(m, B, 64)
    cos, sin = dsv.serving_rope_tables(m, 64, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, m.hidden_size))
    leaves = {n: cache[n][:1] for n in ("ckv", "ki")}
    for b in range(B):
        leaves = {n: v for n, v in dsv.attention(
            lp, x[b:b + 1], cos[:S], sin[:S], m,
            {**leaves, "slot": jnp.int32(b)}, jnp.zeros((1,), jnp.int32), 0,
            jnp.ones((1, S), bool))[1].items() if n != "slot"}
    return m, lp, leaves, (cos, sin)


def test_a_chunk_walks_and_a_decode_step_gathers(monkeypatch):
    """The rule is the call's shapes: a prefill (a chunk of one slot, or a
    whole sequence without a cache) never calls the gather, a decode step
    never calls ``_attend_selected``."""
    calls = []

    def spy(name):
        real = getattr(dsv, name)

        def spying(*args):
            calls.append(name)
            return real(*args)

        monkeypatch.setattr(dsv, name, spying)

    spy("gather_rows")
    spy("_attend_selected")
    m, lp, leaves, (cos, sin) = _one_layer_cache()  # two chunks
    assert calls == ["_attend_selected"] * 2
    del calls[:]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 1, m.hidden_size))
    out, _, selected, scored, attended = dsv.attention(
        lp, x, cos[40][None, None], sin[40][None, None], m, dict(leaves),
        jnp.asarray([40, 40], jnp.int32), 0, jnp.ones((2, 1), bool))
    assert calls == ["gather_rows"]
    assert (int(selected), int(scored), int(attended)) == (32, 82, 32)
    del calls[:]
    *_, attended = dsv.attention(
        lp, x[:1].repeat(40, axis=1), cos[:40], sin[:40], m, None, None,
        None, jnp.ones((1, 40), bool))
    assert calls == ["_attend_selected"]
    assert int(attended) == 40 * 41 // 2


def test_a_gather_off_by_one_row_fails_the_float32_check(monkeypatch):
    """``correct``'s kind of check can see a wrong gather: with every row
    index of the decode steps shifted by one, the logits of the four decode
    steps leave the float32 reference (the prefill's do not: a chunk never
    gathers)."""
    _, sound, params = make_engine()
    seq, got = program_logits(sound, params, PROMPT)
    want = reference_rows(params, seq, len(PROMPT), TOY)
    assert worst_rel_err(got, want) < 1e-3
    real = dsv.gather_rows
    monkeypatch.setattr(dsv, "gather_rows",
                        lambda leaf, layer, rows: real(leaf, layer, rows + 1))
    _, engine, _ = make_engine(fresh=True)  # traced under the fault
    _, faulty = program_logits(engine, params, PROMPT,
                               follow=seq[len(PROMPT):])
    assert worst_rel_err(faulty[:1], want[:1]) < 1e-3
    assert worst_rel_err(faulty[1:], want[1:]) > 1e-2


# ---- (b) the share adds up to the uncut layer ------------------------------


def test_the_shares_add_up_to_the_uncut_expert_layer():
    uncut = dict(TOY, n_routed_experts=8, ep_size=1, ep_rank=0)
    m_full = make_config(uncut).model
    full = jax.jit(lambda k: dsv.init_params(k, m_full))(
        jax.random.PRNGKey(11))
    lp = jax.tree.map(lambda v: v[0], full["layers"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 128), jnp.float32)
    want = np.asarray(ref.experts(lp, x[0], ref_config(uncut)))
    shared = np.asarray(ref.swiglu(x[0], lp["ws_gate"], lp["ws_up"],
                                   lp["ws_down"]))
    live = jnp.ones((1, 24), bool)
    total = shared.copy()  # what every chip computes alike, counted once
    held = 0
    for rank in range(4):
        m = make_config(dict(TOY, ep_rank=rank)).model
        part = {**lp, **{n: lp[n][2 * rank:2 * rank + 2]
                         for n in ("w1", "w3", "w2")}}
        y, (assigned, *_) = dsv.expert_mlp(part, x, m, live)
        total += np.asarray(y[0]) - shared
        held += int(assigned)
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert held == 24 * 2  # every token's experts are held by some rank


# ---- (c) the router by hand -------------------------------------------------
# (``experts.route`` since PR 39: the afmoe block calls it too)


def route(scores, bias, m):
    """The router as ``deepseek_v32.expert_mlp`` calls it."""
    return experts.route(scores, bias, k=m.num_experts_per_tok,
                         n_group=m.n_group, topk_group=m.topk_group,
                         scale=m.routed_scaling_factor)


def test_router_by_hand():
    shape = dict(n_group=4, topk_group=2, num_experts_per_tok=3)
    m = make_config(dict(TOY, n_routed_experts=12, ep_size=1, **shape)).model
    # binary fractions, so that a tie is a tie in float32 too
    scores = jnp.asarray([[0.875, 0.75, 0.125,      # group 0: 1.625, kept
                           0.9375, 0.125, 0.125,    # group 1: 1.0625
                           0.5625, 0.5625, 0.5625,  # group 2: 1.125
                           0.6875, 0.625, 0.0]])    # group 3: 1.3125, kept
    experts, w = route(scores, jnp.zeros(12), m)
    # the group limit: expert 3 has the best score and is not chosen
    assert experts.tolist() == [[0, 1, 9]]
    np.testing.assert_allclose(
        w[0], np.array([0.875, 0.75, 0.6875]) / 2.3125 * 2.5, rtol=1e-6)
    assert math.isclose(float(w.sum()), 2.5, rel_tol=1e-6)
    # the bias moves the choice: +0.5 on expert 5 lifts group 1 (1.5625)
    # over group 3, and expert 3 comes in; the weights stay the unbiased
    # scores of the chosen
    experts, w = route(scores, jnp.zeros(12).at[5].set(0.5), m)
    assert experts.tolist() == [[3, 0, 1]]
    np.testing.assert_allclose(
        w[0], np.array([0.9375, 0.875, 0.75]) / 2.5625 * 2.5, rtol=1e-6)
    # +0.75 on expert 4 makes it 0.875 biased: a tie with expert 0, which
    # goes to the lower index; expert 4 is chosen third and weighs its
    # unbiased 0.125
    bias = jnp.zeros(12).at[4].set(0.75)
    experts, w = route(scores, bias, m)
    assert experts.tolist() == [[3, 0, 4]]
    np.testing.assert_allclose(
        w[0], np.array([0.9375, 0.875, 0.125]) / 1.9375 * 2.5, rtol=1e-6)
    assert math.isclose(float(w.sum()), 2.5, rel_tol=1e-6)
    # the reference's router agrees
    r_experts, r_w = ref.route(scores, bias, ref_config(dict(
        TOY, n_routed_experts=12, ep_size=1, **shape)))
    assert r_experts.tolist() == experts.tolist()
    np.testing.assert_allclose(r_w, w, rtol=1e-6)
    # the share of rank 1 of 4, which holds experts 3-5
    held = dsv.held_weights(experts, w, make_config(dict(
        TOY, n_routed_experts=3, ep_size=4, ep_rank=1, **shape)).model)
    np.testing.assert_allclose(
        held[0], np.array([0.9375, 0.125, 0.0]) / 1.9375 * 2.5, rtol=1e-6)


def test_router_without_groups_takes_the_best_of_the_whole_width():
    """``n_group = topk_group = 1`` (the afmoe block's router): no group
    limit, the same bias-in-the-choice-only rule, ties to the lower index;
    ``eps`` joins the normalising sum."""
    scores = jnp.asarray([[0.875, 0.75, 0.125, 0.9375, 0.125, 0.125,
                           0.5625, 0.5625, 0.5625, 0.6875, 0.625, 0.0]])
    chosen, w = experts.route(scores, jnp.zeros(12), k=3, scale=2.5)
    # expert 3, which the group limit above kept out, leads
    assert chosen.tolist() == [[3, 0, 1]]
    np.testing.assert_allclose(
        w[0], np.array([0.9375, 0.875, 0.75]) / 2.5625 * 2.5, rtol=1e-6)
    grouped, wg = experts.route(scores, jnp.zeros(12), k=3, n_group=1,
                                topk_group=1, scale=2.5, eps=1e-20)
    assert grouped.tolist() == chosen.tolist()
    np.testing.assert_array_equal(np.asarray(wg), np.asarray(w))
    # a bias of 0.3125 on expert 6 ties it with expert 0 behind expert 3:
    # the lower index first, and it weighs its unbiased 0.5625
    chosen, w = experts.route(scores, jnp.zeros(12).at[6].set(0.3125), k=3)
    assert chosen.tolist() == [[3, 0, 6]]
    np.testing.assert_allclose(
        w[0], np.array([0.9375, 0.875, 0.5625]) / 2.375, rtol=1e-6)


# ---- (d) the selection ------------------------------------------------------


def test_selection_is_exact_with_ties_to_the_lower_index():
    inf = -jnp.inf
    scores = jnp.asarray([[[0.5, 0.9, 0.5, 0.9, 0.1, inf, inf],
                           [-0.5, -0.25, 0.0, -0.25, -0.25, -2.0, inf]]])
    picked = lambda k: [np.flatnonzero(r).tolist()
                        for r in np.asarray(dsv.select_keys(scores, k))[0]]
    # of the two 0.5 the lower index; of the three -0.25 the two lower
    assert picked(3) == [[0, 1, 3], [1, 2, 3]]
    assert picked(1) == [[1], [2]]
    # a query that sees fewer than k keys takes them all, and no -inf
    assert picked(6) == [[0, 1, 2, 3, 4], [0, 1, 2, 3, 4, 5]]
    assert picked(7) == [[0, 1, 2, 3, 4], [0, 1, 2, 3, 4, 5]]
    # against a stable sort, on random scores with many ties
    rng = np.random.default_rng(0)
    s = rng.integers(-4, 5, (1, 64, 300)).astype(np.float32) / 4
    s[0, :, 200:] = -np.inf
    s[0, 5, 3:] = -np.inf
    got = np.asarray(dsv.select_keys(jnp.asarray(s), 40))
    order = np.argsort(-s, axis=-1, kind="stable")[..., :40]
    want = np.zeros_like(got)
    np.put_along_axis(want, order, True, axis=-1)
    want &= s > -np.inf
    assert (got == want).all()


def test_index_scores_are_causal():
    key = jax.random.PRNGKey(2)
    qi = jax.random.normal(key, (1, 3, 4, 16))
    ki = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 10, 16))
    wi = jnp.ones((1, 3, 4))
    pos_q = jnp.asarray([[4, 5, 6]])
    s = dsv.index_scores(qi, wi, {"ki": ki}, 0, pos_q)
    assert s.shape == (1, 3, 10)
    for i, p in enumerate([4, 5, 6]):
        assert bool(jnp.all(jnp.isfinite(s[0, i, :p + 1])))
        assert bool(jnp.all(s[0, i, p + 1:] == -jnp.inf))
    want = jnp.sum(jax.nn.relu(jnp.einsum("shd,td->sht", qi[0], ki[0, 0])),
                   axis=1)
    np.testing.assert_allclose(s[0, 2, :7], want[2, :7], rtol=1e-5)


def test_a_chunk_boundary_changes_nothing():
    _, e_chunks, params = make_engine(prefill_chunk=32)
    _, e_whole, _ = make_engine(prefill_chunk=256)
    _, a = program_logits(e_chunks, params, PROMPT)
    _, b = program_logits(e_whole, params, PROMPT)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=2e-5)
    sa, sb = (dict(zip(dsv.STAT_NAMES, e.take_stats()))
              for e in (e_chunks, e_whole))
    # the same keys selected and scored, the same held assignments,
    # whatever the number of programs (and so of expert-layer steps)
    for name in ("moe_layer_steps", "moe_experts_hit", "moe_expert_rows",
                 "moe_pipelined_steps"):
        sa.pop(name), sb.pop(name)
    assert sa == sb


# ---- (e) YaRN ---------------------------------------------------------------


def test_yarn_tables_and_mscale_closed_form():
    published = dict(YARN, original_max_position_embeddings=4096)
    inv = rope.yarn_inv_freq(64, 10000.0, published)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # correction dims: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47 -> 10,
    # 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 -> 23
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-12)
    np.testing.assert_allclose(
        inv[16], plain[16] * ((6 / 13) / 40 + 7 / 13), rtol=1e-12)
    assert math.isclose(rope.yarn_mscale(published),
                        0.1 * math.log(40) + 1)
    m = make_config(dict(TOY, qk_nope_head_dim=128, qk_rope_head_dim=64,
                         index_head_dim=128, rope_scaling=published)).model
    assert math.isclose(dsv.softmax_scale(m),
                        192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2)
    assert math.isclose(dsv.softmax_scale(m), 0.1352338, rel_tol=1e-5)
    cos, sin = dsv.serving_rope_tables(m, 8, jnp.float32)
    np.testing.assert_allclose(cos[5, :32], np.cos(5 * inv), rtol=1e-5)
    np.testing.assert_allclose(sin[5, 32:], np.sin(5 * inv), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(ref.yarn_inv_freq(64, 10000.0, published),
                               inv, rtol=1e-12)
    # no scaling: the plain tables and the plain scale
    assert math.isclose(dsv.softmax_scale(
        make_config(dict(TOY, rope_scaling=None)).model), 24 ** -0.5)


def test_interleaved_rope_pairs_adjacent_elements():
    x = jnp.arange(8, dtype=jnp.float32).reshape(1, 1, 1, 8)
    cos, sin = rope.precompute_rope(4, 8, 10000.0, jnp.float32)
    got = rope.apply_rope_interleaved(x, cos[None, 3:4], sin[None, 3:4])
    ang = 3 * 10000.0 ** (-np.arange(0, 8, 2) / 8)
    a, b = np.arange(0, 8, 2), np.arange(1, 8, 2)
    want = np.stack([a * np.cos(ang) - b * np.sin(ang),
                     a * np.sin(ang) + b * np.cos(ang)], -1).reshape(8)
    np.testing.assert_allclose(got[0, 0, 0], want, rtol=1e-5)


# ---- (f) what is refused, by name ------------------------------------------


@pytest.mark.parametrize("impl", ["auto", "dense"])
def test_validate_accepts_attend_impl(impl):
    """``auto`` (the shipped default) and ``dense`` pass; an explicit
    ``flash`` is refused above."""
    assert make_config().inference.attend_impl == "auto"
    cfg = make_config(inference={"attend_impl": impl})
    assert cfg.inference.attend_impl == impl


def test_training_is_refused_by_name():
    from picotron_tpu import train_step as ts
    from picotron_tpu.topology import topology_from_config

    cfg = make_config()
    cfg.validate()  # serving: fine
    with pytest.raises(ValueError, match="served, not trained"):
        cfg.validate(for_training=True)
    topo = topology_from_config(cfg)
    with pytest.raises(ValueError, match="served, not trained"):
        ts.init_state(cfg, topo)
    with pytest.raises(ValueError, match="served, not trained"):
        ts.build_train_step(cfg, topo)


@pytest.mark.parametrize("model,match", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    ({"ep_rank": 4}, "ep_rank"),
    ({"n_group": 3}, "router's width"),
    ({"rope_scaling": {"type": "linear", "factor": 2}}, "yarn"),
    ({"model_type": "deepseek_v4"}, "unknown model_type"),
])
def test_validate_refuses_what_the_block_lacks(model, match):
    with pytest.raises(ValueError, match=match):
        make_config(model)


def test_engine_keywords_are_refused_too():
    with pytest.raises(ValueError, match="kv_layout 'paged'"):
        InferenceEngine(make_config(), slots=2, max_seq_len=64,
                        kv_layout="paged")
    with pytest.raises(ValueError, match="speculation"):
        InferenceEngine(make_config(), slots=2, max_seq_len=64, spec_len=2)


# ---- (g) the serving control -------------------------------------------------


def test_bfloat16_fails_the_float32_check():
    """The program in the nearest precision below fails the 1e-3 the
    float32 engine is held to, read along the tokens the sound run chose."""
    _, engine, params = make_engine()
    seq, got = program_logits(engine, params, PROMPT)
    want = reference_rows(params, seq, len(PROMPT), TOY)
    assert worst_rel_err(got, want) < 1e-3
    _, low, _ = make_engine({"dtype": "bfloat16"})
    low_params = jax.tree.map(lambda v: v.astype(jnp.bfloat16), params)
    low_params["layers"]["router_bias"] = params["layers"]["router_bias"]
    _, got_low = program_logits(low, low.shard_params(low_params), PROMPT,
                                follow=seq[len(PROMPT):])
    assert worst_rel_err(got_low, want) > 1e-3


@pytest.mark.parametrize("control", ["_fp8_indexer", "_fp8_weights"])
def test_the_reference_in_fp8_fails_the_float32_check(control):
    """The reference computed in the published model's FP8 (the indexer's
    vectors, or every layer's matrices, rounded to E4M3) is another model
    to the check: the sound program read against it is not correct."""
    _, engine, params = make_engine()
    seq, got = program_logits(engine, params, PROMPT)
    assert worst_rel_err(got, reference_rows(params, seq, len(PROMPT),
                                             TOY)) < 1e-3
    low = reference_rows(params, seq, len(PROMPT), {**TOY, control: True})
    assert worst_rel_err(got, low) > 1e-2


def test_e4m3_in_float32_arithmetic_is_the_types_own_rounding():
    rng = np.random.default_rng(0)
    x = np.concatenate([
        rng.normal(size=4096) * 10.0 ** rng.uniform(-5, 2.6, 4096),
        [0.0, 448.0, -448.0, 2.0 ** -6, 2.0 ** -9, 2.0 ** -10, 0.0185,
         1.0625, 1.1875, 3e-4, -17.0]]).astype(np.float32)
    x = np.clip(x, -448, 448)
    want = np.asarray(jnp.asarray(x).astype(jnp.float8_e4m3fn)
                      .astype(jnp.float32))
    assert np.array_equal(np.asarray(ref.e4m3(jnp.asarray(x))), want)


def test_fp8_rounding_keeps_e4m3_values_under_power_of_two_scales():
    w = jax.random.normal(jax.random.PRNGKey(0), (2, 200, 130),
                          jnp.bfloat16)
    r = ref.fp8_blocks(w)
    assert r.shape == w.shape and r.dtype == w.dtype
    w32, r32 = np.asarray(w, np.float32), np.asarray(r, np.float32)
    # E4M3 keeps three mantissa bits: within 2^-4 of the value, or, under
    # 2^-6 of the block's scale, a subnormal step of 2^-9 of it
    assert np.all(np.abs(r32 - w32)
                  <= np.maximum(np.abs(w32) / 16, 2.0 ** -9) + 1e-12)
    assert 1e-3 < np.abs(r32 - w32).max()
    # one block of 128 x 128 scaled by 2^k comes back scaled by 2^k: the
    # scale is a power of two, so bfloat16 holds every rounded value
    again = ref.fp8_blocks((w[0, :128, :128].astype(jnp.float32)
                            * 2.0 ** 7).astype(jnp.bfloat16))
    assert np.array_equal(np.asarray(again, np.float32),
                          r32[0, :128, :128] * 2.0 ** 7)


def test_seeded_attention_is_loud_and_a_routed_expert_quiet():
    """``INIT_GAIN``: the attention's output projection is drawn wider than
    U(+-sqrt(1 / fan_in)), the routed experts' down projection narrower,
    every other matrix at it."""
    m = make_config().model
    params = jax.jit(lambda k: dsv.init_params(k, m))(jax.random.PRNGKey(1))
    layers = params["layers"]
    for name, fan_in in (("wq_b", m.q_lora_rank),
                         ("wo", m.num_attention_heads * m.v_head_dim),
                         ("wkv_b", m.kv_lora_rank), ("w1", m.hidden_size),
                         ("w2", m.moe_intermediate_size)):
        bound = dsv.INIT_GAIN.get(name, 1.0) * math.sqrt(1.0 / fan_in)
        top = float(jnp.max(jnp.abs(layers[name])))
        assert 0.97 * bound < top <= bound, name
    assert dsv.INIT_GAIN == {"wo": 4.0, "w2": 0.5}


def test_stats_leave_the_programs_a_row_a_layer():
    """No int32 is summed over layers inside a program: the stats come out
    [layers, counters] and the host adds them up in int64."""
    _, engine, params = make_engine(prefill_chunk=256)
    engine.prefill(params, PROMPT)
    pending, = engine._stats_pending
    assert pending.shape == (3, len(dsv.STAT_NAMES))
    assert pending.dtype == jnp.int32
    rows = dict(zip(dsv.STAT_NAMES, np.asarray(pending).T))
    assert (rows["dsa_keys_scored"]
            == (np.arange(len(PROMPT)) + 1).sum()).all()
    # the dense layer routes nothing
    assert list(rows["moe_layer_steps"]) == [0, 1, 1]
    total = engine.take_stats()
    assert total.dtype == np.int64
    assert (total == np.asarray(pending).sum(axis=0)).all()


# ---- (h) the cell's rehearsal -------------------------------------------------


def test_rehearsal_of_the_cell_computes_its_readers():
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "2", "--trace", "2", "--rehearse"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    assert {"serve_out_tokens_per_s", "serve_itl_p99_ms", "setup_s",
            "moe.held_assignments_per_step", "dsa.selected_pct",
            "dsa.attended_rows_pct.dsv32",
            "batcher.dispatch_gap_ms", "batcher.plan_ms",
            "batcher.deliver_ms", "front.loop_lock_wait_ms",
            "front.results_ms"} <= set(out["computed"])
    # Keye's readers take the counter this block now shares as the mark of
    # Keye's block: ``run.py`` confines each to its own cells
    assert not [n for n in out["computed"] if n.endswith(".keye")]


def test_the_reader_gives_nothing_for_a_program_without_the_counter():
    """The parent's program: the block's two older counters, and no
    ``picotron_dsa_rows_attended_total``."""
    sys.path.insert(0, ROOT)
    from benchmarks.run import load_reader

    read = load_reader("layer_metrics", "dsa.attended_rows_pct.dsv32")
    scrape = ("picotron_dsa_keys_scored_total {}\n"
              "picotron_dsa_keys_selected_total {}\n")
    run = {"metrics_before": scrape.format(1000, 500),
           "metrics_after": scrape.format(9000, 1500)}
    assert read(run) is None
    assert read({}) is None
    attended = "picotron_dsa_rows_attended_total {}\n"
    run = {"metrics_before": run["metrics_before"] + attended.format(500),
           "metrics_after": run["metrics_after"] + attended.format(1500)}
    assert read(run) == pytest.approx(12.5)
    run["metrics_after"] = run["metrics_before"]  # no step in the window
    assert read(run) is None
