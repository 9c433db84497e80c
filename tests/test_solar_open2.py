"""The Solar Open 2 block (models/solar_open2.py) on the serving path, at toy
size in float32 on the CPU with seeded weights, against the plain reference
(benchmarks/reference/solar_open2.py): the engine's programs through a cache
of three kinds of leaf, Kimi-delta-attention layers through ``ops/kda.py``
(the chunked form against the recurrence a token at a time, under decays
hard enough to overflow a careless one), gated NoPE GQA layers, the expert
share at the block's own widths, what a state with no token axis asks of the
programs, and what ``Config.validate`` refuses."""

from functools import partial
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import block_toys
from engine_memo import (admit, decode, memoized, program_logits,
                         worst_rel_err)

from picotron_tpu.config import Config
from picotron_tpu.inference import InferenceEngine
from picotron_tpu.models import experts, model_module, runs
from picotron_tpu.models import solar_open2 as so
from picotron_tpu.ops.kda import kda_scan, kda_step
from picotron_tpu.ops.pallas import grouped_experts as grouped

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED_GQA = list(range(0, 48, 4))

TOY = block_toys.TOYS["solar_open2"]
N_KDA, N_GQA = 6, 2  # layers of TOY's pattern


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_solar_open2",
        os.path.join(ROOT, "benchmarks", "reference", "solar_open2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


make_config = partial(block_toys.make_config, "solar_open2")


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, slots=2, max_seq_len=128,
                             **{"prefill_chunk": 16, **kw})
    params = jax.jit(lambda k: so.init_params(k, cfg.model))(
        jax.random.PRNGKey(7))
    return cfg, engine, engine.shard_params(params)


def reference_rows(params, seq, n_prompt, model=TOY):
    return ref.forward_logits(params, np.asarray([seq], np.int32),
                              dict(model), jax.devices()[0])[0][n_prompt - 1:]


RNG = np.random.default_rng(5)
PROMPT = [int(t) for t in RNG.integers(1, 256, 44)]
OTHER = [int(t) for t in RNG.integers(1, 256, 44)]


# ---- (a) the engine's programs against the reference ----------------------


@pytest.mark.parametrize("n_prompt,chunk", [
    (44, 16),   # three chunks: state and conv tails carried twice
    (44, 64),   # the one-shot program, 20 pad rows in its bucket
    (16, 16),   # a whole bucket, no pad row
    (33, 32),   # a second chunk of one token
])
def test_prefill_and_decode_match_the_reference(n_prompt, chunk):
    _, engine, params = make_engine(prefill_chunk=chunk)
    prompt = PROMPT[:n_prompt]
    seq, got, _ = program_logits(engine, params, prompt)
    want = reference_rows(params, seq, n_prompt)
    assert worst_rel_err(got, want) < 1e-3
    stats = dict(zip(so.STAT_NAMES, engine.take_stats()))
    assert stats["kda_tokens_scanned"] == N_KDA * n_prompt
    assert stats["kda_state_updates"] == stats["kda_layer_steps"] \
        == N_KDA * 4
    # 2 of a router 6 wide a token and layer; this rank holds 3
    assert 0 < stats["moe_assignments"] <= 2 * 8 * (n_prompt + 4)
    assert stats["moe_experts_hit"] <= 3 * stats["moe_layer_steps"]


@pytest.mark.parametrize("model", [
    {},
    {"kda_use_full_proj": True},     # one matrix for the decay, one the gate
    {"kda_allow_neg_eigval": False},  # b in (0, 1)
    {"use_gqa_gate": False},
])
def test_the_whole_forward_matches_the_reference_at_every_position(model):
    cfg, engine, params = make_engine(model, prefill_chunk=64)
    tokens = jnp.asarray([PROMPT])

    def forward(params, tokens):
        h = engine._embed(params, tokens)
        live = jnp.ones(tokens.shape, bool)
        h, _, _ = engine._prefill_groups(params, h, engine._cos,
                                         engine._sin, live)
        return so.head_logits(params, h, cfg)

    from jax.sharding import PartitionSpec as P

    from picotron_tpu.utils import shard_map
    got = jax.jit(shard_map(forward, engine.topo.mesh,
                            in_specs=(engine._pspecs, P()),
                            out_specs=P()))(params, tokens)
    want = ref.forward_logits(params, np.asarray([PROMPT]),
                              dict(TOY, **model))
    assert "lm_head" in params  # untied, as published
    assert ("w_f" in params["kda_1"]) == bool(model.get("kda_use_full_proj"))
    assert ("wg" in params["gqa_0"]) == model.get("use_gqa_gate", True)
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=1e-3 * np.abs(want).max())


def test_a_chunk_boundary_changes_nothing():
    _, e_chunks, params = make_engine(prefill_chunk=16)
    _, e_whole, _ = make_engine(prefill_chunk=64)
    _, a, ca = program_logits(e_chunks, params, PROMPT)
    _, b, cb = program_logits(e_whole, params, PROMPT)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=1e-5)
    for name in ("kda", "conv"):
        np.testing.assert_allclose(ca[name][:, 0], cb[name][:, 0], atol=1e-5,
                                   rtol=1e-5)


# ---- (b) the recurrence: the chunked form against a token at a time --------


def _draw(S, hard, B=2, nh=3, K=16, V=8):
    ks = jax.random.split(jax.random.PRNGKey(S), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, nh, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, nh, K)))
    v = jax.random.normal(ks[2], (B, S, nh, V))
    # log-uniform decays; hard: up to e^2.2 = 9 a row and channel
    g = -jnp.exp(jax.random.uniform(ks[3], (B, S, nh, K), minval=-6.0,
                                    maxval=2.2 if hard else 0.0))
    if hard:  # one channel of every head falls by e^-8 a row
        g = g.at[..., 0].set(-8.0)
    b = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, nh)))
    # rows that are not live freeze the state
    g, b = g.at[1, S - 3:].set(0.0), b.at[1, S - 3:].set(0.0)
    return q, k, v, g, b, jax.random.normal(ks[5], (B, nh, K, V))


@pytest.mark.parametrize("S,chunk,hard", [
    (37, 8, False), (64, 64, False), (5, 8, True),
    (150, 64, True),   # two sub-chunk boundaries, a padded third sub-chunk
    (40, 16, True)])
def test_the_chunked_form_is_the_recurrence_a_token_at_a_time(S, chunk,
                                                              hard):
    """``kda_scan`` against ``kda_step`` from a state that is not zero, with
    negative eigenvalues (b up to 2). ``hard``: a sub-chunk's ``cumsum g``
    passes -88 on some channel, so ``exp(-cumsum g)`` alone would be inf and
    a form built on it nan; every exponent here is a difference."""
    q, k, v, g, b, S0 = _draw(S, hard)
    if hard and S >= 16:
        fall = jnp.min(jnp.sum(g[:, :min(chunk, S)], axis=1))
        assert not bool(jnp.isfinite(jnp.exp(-fall))), fall
    o, state = jax.jit(kda_scan, static_argnums=6)(q, k, v, g, b, S0, chunk)
    want, s = [], S0
    for t in range(S):
        cut = lambda a: a[:, t:t + 1]
        o_t, s = kda_step(cut(q), cut(k), cut(v), cut(g), cut(b), s)
        want.append(o_t)
        if t == S - 4:
            frozen = s[1]
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(state).all())
    np.testing.assert_allclose(o, jnp.concatenate(want, axis=1), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(state, s, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(frozen))


def test_the_step_is_the_four_lines_as_written():
    """``kda_step`` reads out along ``q`` from the decayed state and adds
    what the write adds; the recurrence as written reads the new state.
    With a ``row`` it advances that row of the stacked leaf alone."""
    q, k, v, g, b, S0 = _draw(1, False)
    decayed = jnp.exp(g[:, 0])[..., None] * S0
    was = jnp.einsum("bhkv,bhk->bhv", decayed, k[:, 0])
    new = decayed + b[:, 0, :, None, None] * k[:, 0, ..., None] \
        * (v[:, 0] - was)[..., None, :]
    o, state = kda_step(q, k, v, g, b, S0)
    np.testing.assert_allclose(state, new, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(
        o[:, 0], jnp.einsum("bhkv,bhk->bhv", new, q[:, 0]), atol=1e-6)
    leaf = jnp.stack([S0 + 1.0, S0, S0 - 1.0])
    o_r, out = jax.jit(kda_step)(q, k, v, g, b, leaf, jnp.asarray(1))
    np.testing.assert_allclose(o_r, o, atol=1e-6)
    np.testing.assert_allclose(out[1], state, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(leaf[0]))
    np.testing.assert_array_equal(np.asarray(out[2]), np.asarray(leaf[2]))


def test_the_mixer_is_the_references():
    """The KDA mixer alone against the reference's: three convolutions as
    one, the L2 norms, the low-rank decay and gate, the head's norm."""
    cfg, _, params = make_engine()
    m = cfg.model
    lp = jax.tree.map(lambda v: v[1], {
        n: v for n, v in params["kda_1"].items()
        if n not in ("w1", "w2", "w3")})
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 23, 64), jnp.float32)
    live = jnp.ones((1, 23), bool)
    got, tail, state = so.kda_mixer(
        lp, x, jnp.zeros((1, 3, so.conv_width(m))),
        jnp.zeros((1, 4, 16, 16)), live, m, one_step=())
    want = ref.kda(lp, x[0], dict(TOY))
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # the tail is the last three rows of the projections, before the conv
    np.testing.assert_allclose(tail[0], (x[0] @ lp["wqkv"])[-3:], atol=1e-6)
    assert state.shape == (1, 4, 16, 16) and state.dtype == jnp.float32
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(
        (x[0] @ lp["w_fa"]) @ lp["w_fb"] + lp["dt_bias"]).reshape(23, 4, 16)
    assert float(g.max()) < 0.0  # every head and key channel decays


# ---- (c) a state with no token axis ----------------------------------------


def test_pad_rows_leave_state_and_conv_tail_as_at_length():
    _, padded, params = make_engine(prefill_chunk=64)  # 21 -> bucket 32
    _, exact, _ = make_engine(prefill_chunk=64, min_prefill_bucket=21)
    prompt = PROMPT[:21]
    kv_p, last_p = padded.prefill(params, prompt)
    kv_e, last_e = exact.prefill(params, prompt)
    assert kv_p["kda"].shape == (N_KDA, 1, 4, 16, 16)
    assert kv_p["kda"].dtype == jnp.float32
    assert kv_p["conv"].shape == (N_KDA, 1, 3, 3 * 4 * 16)
    assert kv_p["k"].shape == (N_GQA, 1, 32, 2, 16)
    close = dict(atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(kv_p["kda"], kv_e["kda"], **close)
    np.testing.assert_allclose(kv_p["conv"], kv_e["conv"], **close)
    np.testing.assert_allclose(last_p, last_e, **close)
    _, chunks, _ = make_engine(prefill_chunk=16)  # 21 = 16 + 5 of 16
    cache, _ = chunks.prefill_chunked(params, chunks.init_cache(), prompt, 1)
    np.testing.assert_allclose(cache["kda"][:, 1], kv_e["kda"][:, 0], **close)
    np.testing.assert_allclose(cache["conv"][:, 1], kv_e["conv"][:, 0],
                               **close)
    assert not np.asarray(cache["kda"][:, 0]).any()  # the other slot


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_slot_used_twice_forgets_its_first_occupant(chunk):
    _, engine, params = make_engine(prefill_chunk=chunk)
    _, _, cache = program_logits(engine, params, PROMPT)
    assert np.abs(np.asarray(cache["kda"][:, 0])).max() > 0
    cache = engine.release(cache, 0)
    seq, got, _ = program_logits(engine, params, OTHER, cache=cache)
    assert worst_rel_err(got, reference_rows(params, seq, len(OTHER))) < 1e-3


def test_a_parked_slot_is_bit_equal_and_uncounted_in_a_decode_block():
    _, engine, params = make_engine()
    cache, last0 = admit(engine, params, engine.init_cache(), PROMPT, 0)
    cache, last1 = admit(engine, params, cache, OTHER[:30], 1)
    before = {n: np.asarray(cache[n][:, 1]) for n in ("kda", "conv")}
    moved = np.asarray(cache["kda"][:, 0])
    engine.take_stats()
    keys = np.stack([np.asarray(jax.random.PRNGKey(i))
                     for i in range(engine.decode_block_len)])
    toks = np.asarray([np.argmax(last0), np.argmax(last1)], np.int32)
    r = engine.decode_block(
        params, cache, toks, keys, -np.ones(2, np.int32),
        np.array([3, 0], np.int32), np.zeros(2, np.float32),
        np.zeros(2, np.int32), np.ones(2, np.float32))
    assert list(np.asarray(r.counts)) == [3, 0]
    for n in ("kda", "conv"):  # slot 1 is parked and out of budget
        np.testing.assert_array_equal(np.asarray(r.cache[n][:, 1]),
                                      before[n])
    assert np.abs(np.asarray(r.cache["kda"][:, 0]) - moved).max() > 0
    stats = dict(zip(so.STAT_NAMES, engine.take_stats()))
    # 8 steps x 6 KDA layers ran; slot 0 advanced in 3 of the steps
    assert stats["kda_layer_steps"] == 8 * N_KDA
    assert stats["kda_state_updates"] == 3 * N_KDA
    # 8 steps x 8 expert layers; the one live row's held assignments only
    assert stats["moe_layer_steps"] == 8 * 8
    assert 0 < stats["moe_assignments"] <= 2 * 3 * 8
    assert stats["moe_experts_hit"] <= stats["moe_assignments"]
    # slot 1 decodes on from where it stood, as the reference has it
    seq = OTHER[:30] + [int(toks[1])]
    _, logits = decode(engine, params, r.cache, seq[-1], 1)
    assert worst_rel_err([logits], reference_rows(params, seq, 31)) < 1e-3


@pytest.mark.parametrize("rounded", [False, True])
def test_the_state_of_a_bfloat16_model_is_float32_all_the_way(monkeypatch,
                                                              rounded):
    """The serving check's logits may not tell a state kept in bfloat16 from
    the float32 the configuration states. This can: after a chunked
    admission and decode steps of a bfloat16 model next to none of the
    state's entries are ones bfloat16 holds exactly; rounded anywhere on its
    way, all are."""
    if rounded:
        mixer = so.kda_mixer

        def rounding(*args, **kw):
            out, conv_out, state_out = mixer(*args, **kw)
            return out, conv_out, jax.lax.reduce_precision(
                state_out, exponent_bits=8, mantissa_bits=7)

        monkeypatch.setattr(so, "kda_mixer", rounding)
    _, engine, params = make_engine({"dtype": "bfloat16"}, fresh=True)
    _, _, cache = program_logits(engine, params, PROMPT)  # 3 chunks, 4 steps
    state = cache["kda"][:, 0]
    assert state.dtype == jnp.float32 and cache["conv"].dtype == jnp.bfloat16
    there = state != 0
    exact = state.astype(jnp.bfloat16).astype(jnp.float32) == state
    share = float(jnp.sum(exact & there) / jnp.sum(there))
    assert share == 1.0 if rounded else share < 0.01, share


def test_the_window_is_held_to_whole_chunks():
    assert so.CARRIES_STATE
    with pytest.raises(ValueError, match="multiple of prefill_chunk"):
        InferenceEngine(make_config(), slots=2, max_seq_len=120,
                        prefill_chunk=16)


# ---- (d) the shares add up to the uncut layer ------------------------------


def test_the_sixteen_shares_add_up_to_the_uncut_expert_layer():
    """Each rank's routed part by itself, the shared expert, which every
    chip computes alike, counted once."""
    cut = dict(TOY, n_routed_experts=2, ep_size=16, num_experts_per_tok=8)
    uncut = dict(cut, n_routed_experts=32, ep_size=1, ep_rank=0)
    m_full = make_config(uncut).model
    full = jax.jit(lambda k: so.init_params(k, m_full))(
        jax.random.PRNGKey(11))
    lp = jax.tree.map(lambda v: v[0], full["gqa_0"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    want = np.asarray(ref.experts(lp, x[0], uncut))
    shared = np.asarray(ref._swiglu(x[0], lp["ws_gate"], lp["ws_up"],
                                    lp["ws_down"]))
    live = jnp.ones((1, 24), bool)
    total, held = shared.copy(), 0
    for rank in range(16):
        part = {**lp, **{n: lp[n][2 * rank:2 * rank + 2]
                         for n in ("w1", "w3", "w2")}}
        y, counted = so.expert_mlp(
            part, x, make_config(dict(cut, ep_rank=rank)).model, live)
        total += np.asarray(y[0]) - shared
        held += int(counted[0])
    np.testing.assert_allclose(total, want, atol=1e-4 * np.abs(want).max())
    assert held == 24 * 8  # every token's experts are held by some rank


def test_router_is_sigmoid_with_a_bias_on_the_choice_alone():
    cfg, _, params = make_engine()
    lp = jax.tree.map(lambda v: v[0], params["gqa_0"])
    x = jax.random.normal(jax.random.PRNGKey(3), (10, 64), jnp.float32)
    chosen, w = ref._route(x, lp["router"], lp["router_bias"], k=2, scale=1.0)
    s = jax.nn.sigmoid(x @ lp["router"])
    got, got_w = experts.route(s, lp["router_bias"], k=2, scale=1.0,
                               eps=so.ROUTE_EPS)
    assert got.tolist() == chosen.tolist()
    np.testing.assert_allclose(got_w, w, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(got_w, axis=-1), 1.0, rtol=1e-5)
    assert lp["router_bias"].dtype == jnp.float32
    assert 0 < float(jnp.abs(lp["router_bias"]).max()) <= so.ROUTER_BIAS


# ---- (e) the expert share at the block's own widths ------------------------


def _wide_case(N, held=2, H=4096, I=1280, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + N), 4)
    draw = lambda k, shape: (jax.random.normal(k, (1, held) + shape)
                             * shape[0] ** -0.5).astype(jnp.bfloat16)
    lp = {"w1": draw(ks[0], (H, I)), "w3": draw(ks[1], (H, I)),
          "w2": draw(ks[2], (I, H)), "row": jnp.asarray(0, jnp.int32)}
    x = jax.random.normal(ks[3], (N, H)).astype(jnp.bfloat16)
    rng = np.random.default_rng(N)
    w = rng.uniform(0.05, 1.0, (N, held)) * (rng.uniform(0, 1, (N, held))
                                             < 0.5)
    return lp, x, jnp.asarray(w, jnp.float32)


@pytest.mark.parametrize("N", [16, 256])
def test_experts_of_1280_at_4096_go_the_loop_and_grouped_in_blocks_of_640(N):
    """The cell's shape through the share's rules as they are: an expert's
    three matrices (31.5 MB) do not go whole through the kernel's weight
    budget, so a decode block's rows take the loop, and the grouped call
    reads 1,280 in two blocks of 640, five lane tiles each."""
    H, I = 4096, 1280
    assert not experts.takes_pipelined(64, H, I, 2)
    assert not grouped.fits(H, I, 2) and grouped._block_i(H, I, 2) == 640
    lp, x, w_held = _wide_case(N)
    got, run, pipelined = jax.jit(
        lambda *a: experts.routed_experts(*a))(x, w_held, lp)
    f = lambda v: jnp.asarray(v, jnp.float32)
    want = sum(w_held[:, e:e + 1] * experts.swiglu(
        f(x), f(lp["w1"][0, e]), f(lp["w3"][0, e]), f(lp["w2"][0, e]))
        for e in range(2))
    np.testing.assert_allclose(got, want,
                               atol=2e-2 * float(jnp.abs(want).max()))
    assert int(pipelined) == 0
    if experts.takes_grouped(N):
        assert int(run) % grouped.TILE == 0 and int(run) <= 2 * N
    else:
        assert int(run) == 2 * N


# ---- (f) the pattern of layers, the tree, the counters ---------------------


def test_runs_give_the_published_order():
    m = make_config(dict(num_hidden_layers=48, gqa_layers=PUBLISHED_GQA)
                    ).model
    kinds = so.mixers(m)
    assert "".join(k[0].upper() for k in kinds) == "GKKK" * 12
    groups = so.layer_groups(m)
    assert [(n.split("_")[0], c) for n, _, c in groups] \
        == [("gqa", 1), ("kda", 3)] * 12
    for (name, fn, n), (kind, first, kf, count) in zip(groups, runs(kinds)):
        assert fn.keywords == {"first": first, "kind_first": kf}
        assert n == count
    assert groups[5][1].keywords == {"first": 9, "kind_first": 6}
    assert so.kind_counts(m) == {"kda": 36, "gqa": 12}
    cache = jax.eval_shape(lambda: so.init_cache(m, 2, 64))
    assert cache["kda"].shape[:2] == (36, 2) and cache["k"].shape[:2] \
        == (12, 2)


def test_the_tree_and_the_cache_of_the_toy():
    cfg, engine, params = make_engine()
    assert model_module(cfg.model) is so
    assert [(n, c) for n, _, c in so.layer_groups(cfg.model)] == \
        [("gqa_0", 1), ("kda_1", 3), ("gqa_2", 1), ("kda_3", 3)]
    g = params["kda_1"]
    assert g["w1"].shape == g["w3"].shape == (3, 3, 64, 32)
    assert g["wqkv"].shape == (3, 64, 3 * 64)
    assert g["conv_w"].shape == (3, 3 * 64, 4) and "conv_b" not in g
    assert g["w_fa"].shape == (3, 64, 16) and g["w_fb"].shape == (3, 16, 64)
    assert g["w_b"].shape == (3, 64, 4) and g["o_norm"].shape == (3, 16)
    assert g["A_log"].shape == (3, 4) and g["dt_bias"].shape == (3, 64)
    assert g["router"].shape == (3, 64, 6) and g["ws_up"].shape == (3, 64, 32)
    a = params["gqa_0"]
    assert a["wq"].shape == a["wg"].shape == (1, 64, 64)
    assert a["wk"].shape == (1, 64, 32) and "wqkv" not in a
    cache = engine.init_cache()
    assert cache["k"].shape == cache["v"].shape == (N_GQA, 2, 128, 2, 16)
    assert cache["kda"].shape == (N_KDA, 2, 4, 16, 16)
    assert cache["kda"].dtype == jnp.float32
    assert cache["conv"].shape == (N_KDA, 2, 3, 192)
    assert so.num_params(cfg.model) == sum(
        v.size for v in jax.tree.leaves(params))
    A = np.exp(np.asarray(g["A_log"]))
    assert 1.0 <= A.min() and A.max() <= 16.0


def test_stats_leave_the_programs_a_row_a_layer():
    _, engine, params = make_engine(prefill_chunk=64)
    engine.prefill(params, PROMPT)
    pending, = engine._stats_pending
    assert pending.shape == (8, len(so.STAT_NAMES))
    rows = dict(zip(so.STAT_NAMES, np.asarray(pending).T))
    assert list(rows["moe_layer_steps"]) == [1] * 8
    assert list(rows["kda_tokens_scanned"]) == [0, 44, 44, 44] * 2
    assert not rows["kda_state_updates"].any()


def test_the_batcher_puts_the_counters_on_metrics():
    from picotron_tpu.inference import ContinuousBatcher, Request

    _, engine, params = make_engine(fresh=True)
    batcher = ContinuousBatcher(engine, params, seed=0)
    reqs = [Request(uid=f"r{i}", prompt=p, max_new_tokens=5)
            for i, p in enumerate((PROMPT, OTHER[:9], OTHER[:20]))]
    out = batcher.run(reqs)
    assert all(len(out[r.uid].tokens) == 5 for r in reqs)
    text = engine.obs.registry.prometheus()
    got = {}
    for name in so.STAT_NAMES:
        line, = [l for l in text.splitlines()
                 if l.startswith(f"picotron_{name}_total ")]
        got[name] = float(line.split()[1])
    assert got["kda_tokens_scanned"] == N_KDA * (44 + 9 + 20)
    assert got["kda_state_updates"] == N_KDA * 3 * 4
    assert got["moe_layer_steps"] > 0 and got["moe_assignments"] > 0
    _, fresh, _ = make_engine()
    alone = ContinuousBatcher(fresh, params, seed=0).run(
        [Request(uid="x", prompt=OTHER[:20], max_new_tokens=5)])
    assert out["r2"].tokens == alone["x"].tokens


# ---- (g) what is refused, by name ------------------------------------------


@pytest.mark.parametrize("model,match", [
    ({"gqa_layers": None}, "gqa_layers"),
    ({"gqa_layers": [0, 8]}, "gqa_layers"),
    ({"gqa_layers": [0, 5]}, "gqa_interval"),
    ({"gqa_layers": list(range(8))}, "at least one GQA and one KDA"),
    ({"linear_attn_config": None}, "linear_attn_config"),
    ({"linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                             "num_heads": 4, "num_kv_heads": 2}},
     "num_kv_heads"),
    ({"use_rope": True}, "use_rope"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"n_shared_experts": 0}, "n_shared_experts"),
    ({"ep_rank": 2}, "ep_rank"),
    ({"num_experts_per_tok": 7}, "num_experts_per_tok"),
    ({"model_type": "solar"}, "unknown model_type"),
])
def test_validate_refuses_what_the_block_lacks(model, match):
    with pytest.raises(ValueError, match=match):
        make_config(model)


def test_training_and_engine_keywords_are_refused_by_name():
    from picotron_tpu import train_step as ts
    from picotron_tpu.topology import topology_from_config

    cfg = make_config()
    with pytest.raises(ValueError, match="served, not trained"):
        cfg.validate(for_training=True)
    with pytest.raises(ValueError, match="served, not trained"):
        ts.init_state(cfg, topology_from_config(cfg))
    with pytest.raises(ValueError, match="kv_layout 'paged'"):
        InferenceEngine(make_config(), slots=2, max_seq_len=64,
                        kv_layout="paged")
    with pytest.raises(ValueError, match="speculation"):
        InferenceEngine(make_config(), slots=2, max_seq_len=64, spec_len=2)


# ---- (h) the serving control ----------------------------------------------


def test_bfloat16_fails_the_float32_check():
    _, engine, params = make_engine()
    seq, got, _ = program_logits(engine, params, PROMPT)
    want = reference_rows(params, seq, len(PROMPT))
    assert worst_rel_err(got, want) < 1e-3
    _, low, _ = make_engine({"dtype": "bfloat16"})
    low_params = low.shard_params(jax.tree.map(
        lambda v: v.astype(jnp.bfloat16) if v.ndim > 2 or v.shape[-1] > 8
        else v, params))
    cache, last = admit(low, low_params, low.init_cache(), PROMPT)
    got_low = [last]
    for tok in seq[len(PROMPT):]:
        cache, logits = decode(low, low_params, cache, tok)
        got_low.append(logits)
    assert worst_rel_err(got_low, want) > 1e-3


def test_the_configuration_file_is_the_catalogs_row_cut_as_it_says():
    """Every published width as published; the program's tree at the cell's
    size (by shapes) counts what ``opcount_solar.num_params`` counts."""
    import sys
    sys.path.insert(0, ROOT)
    from benchmarks import common, opcount_solar

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "solar-open2-ep16-l8.json")) as f:
        config = json.load(f)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) \
        == (4096, 64, 8, 128)
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (config["moe_intermediate_size"], config["num_experts_per_tok"],
            config["n_shared_experts"],
            config["n_routed_experts"] * config["ep_size"]) \
        == (1280, 8, 1, 320)
    assert (config["use_rope"], config["use_gqa_gate"],
            config["kda_use_full_proj"], config["kda_allow_neg_eigval"]) \
        == (False, True, False, True)
    n = config["num_hidden_layers"]
    assert config["gqa_layers"] == [i for i in PUBLISHED_GQA if i < n]
    assert config["gqa_interval"] == 3
    m = common.model_section(config)
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True}, "model": m,
        "training": {"seq_length": 8192}, "dataset": {"name": "synthetic"}})
    n = so.num_params(cfg.model)
    assert n == opcount_solar.num_params(config)
    assert 3.89e9 < n < 3.91e9
    cache = jax.eval_shape(lambda: so.init_cache(cfg.model, 1, 1))
    per_slot = sum(a.size * a.dtype.itemsize
                   for k, a in cache.items() if k in ("kda", "conv"))
    assert per_slot == opcount_solar.state_bytes_per_slot(config)
    # a program without the block: the first key ModelConfig lacks, exit 2
    config["model_keys"] = config["model_keys"] + ["kda_mystery"]
    config["kda_mystery"] = 1
    with pytest.raises(SystemExit) as e:
        common.model_section(config)
    assert e.value.code == 2
