"""The Nemotron-H block (models/nemotron_h.py) on the serving path, at toy
size in float32 on the CPU with seeded weights, against the plain reference
(benchmarks/reference/nemotron_h.py): the engine's programs through a cache
of three kinds of leaf, layers that are one sublayer stacked by units of the
pattern, Mamba-2 with B and C a group of heads (``ops/ssm.py``), LatentMoE
through the expert share's two-matrix relu^2 form in its three orders
(``models/experts.py``, ``ops/pallas/grouped_experts.py`` in interpret mode),
what a state with no token axis asks of the programs, and what
``Config.validate`` refuses."""

from functools import partial
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import block_toys
from engine_memo import memoized

from picotron_tpu.config import Config
from picotron_tpu.inference import InferenceEngine
from picotron_tpu.models import experts, model_module
from picotron_tpu.models import nemotron_h as nh
from picotron_tpu.ops.pallas import grouped_experts as grouped
from picotron_tpu.ops.ssm import ssm_scan, ssm_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")

TOY = block_toys.TOYS["nemotron_h"]


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_nemotron_h",
        os.path.join(ROOT, "benchmarks", "reference", "nemotron_h.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


make_config = partial(block_toys.make_config, "nemotron_h")


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, slots=2, max_seq_len=128,
                             **{"prefill_chunk": 16, **kw})
    params = jax.jit(lambda k: nh.init_params(k, cfg.model))(
        jax.random.PRNGKey(7))
    return cfg, engine, engine.shard_params(params)


def admit(engine, params, cache, prompt, slot=0):
    if len(prompt) > engine.prefill_chunk:
        cache, last = engine.prefill_chunked(params, cache, prompt, slot)
    else:
        kv, last = engine.prefill(params, prompt)
        cache = engine.insert(cache, kv, slot, len(prompt))
    return cache, np.asarray(last, np.float32)[0]


def decode(engine, params, cache, tok, slot=0):
    toks = np.zeros(engine.slots, np.int32)
    toks[slot] = tok
    cache, _, logits = engine.decode_step(
        params, cache, toks, jax.random.PRNGKey(0),
        np.zeros(engine.slots, np.float32), np.zeros(engine.slots, np.int32),
        np.ones(engine.slots, np.float32))
    return cache, np.asarray(logits, np.float32)[slot]


def program_logits(engine, params, prompt, steps=4, cache=None, slot=0):
    """The runner's check (benchmarks/runners/serve.py::program_logits)."""
    cache = engine.init_cache() if cache is None else cache
    cache, last = admit(engine, params, cache, prompt, slot)
    seq, got = list(prompt), [last]
    for _ in range(steps):
        seq.append(int(np.argmax(got[-1])))
        cache, logits = decode(engine, params, cache, seq[-1], slot)
        got.append(logits)
    return seq, got, cache


def worst_rel_err(got, want) -> float:
    return max(float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
               for g, w in zip(got, want))


def reference_rows(params, seq, n_prompt, model=TOY):
    return ref.forward_logits(params, np.asarray([seq], np.int32),
                              dict(model), jax.devices()[0])[0][n_prompt - 1:]


RNG = np.random.default_rng(5)
PROMPT = [int(t) for t in RNG.integers(1, 256, 44)]
OTHER = [int(t) for t in RNG.integers(1, 256, 44)]
N_M, N_E = 3, 3  # sublayers of TOY's pattern


# ---- (a) the engine's programs against the reference ----------------------


@pytest.mark.parametrize("n_prompt,chunk", [
    (44, 16),   # three chunks: state and conv tail carried twice
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
    stats = dict(zip(nh.STAT_NAMES, engine.take_stats()))
    assert stats["ssm_tokens_scanned"] == N_M * n_prompt
    assert stats["ssm_state_updates"] == stats["ssm_layer_steps"] == N_M * 4
    # 2 of a router 6 wide a token and expert layer; this rank holds 3
    assert 0 < stats["moe_assignments"] <= 2 * N_E * (n_prompt + 4)


def test_the_whole_forward_matches_the_reference_at_every_position():
    cfg, engine, params = make_engine(prefill_chunk=64)
    tokens = jnp.asarray([PROMPT])

    def forward(params, tokens):
        h = engine._embed(params, tokens)
        live = jnp.ones(tokens.shape, bool)
        h, _, _ = engine._prefill_groups(params, h, engine._cos,
                                         engine._sin, live)
        return nh.head_logits(params, h, cfg)

    from jax.sharding import PartitionSpec as P

    from picotron_tpu.utils import shard_map
    got = jax.jit(shard_map(forward, engine.topo.mesh,
                            in_specs=(engine._pspecs, P()),
                            out_specs=P()))(params, tokens)
    want = ref.forward_logits(params, np.asarray([PROMPT]), dict(TOY))
    assert "lm_head" in params  # untied, as published
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=1e-3 * np.abs(want).max())


def test_a_chunk_boundary_changes_nothing():
    _, e_chunks, params = make_engine(prefill_chunk=16)
    _, e_whole, _ = make_engine(prefill_chunk=64)
    _, a, ca = program_logits(e_chunks, params, PROMPT)
    _, b, cb = program_logits(e_whole, params, PROMPT)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=1e-5)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(ca[name][:, 0], cb[name][:, 0], atol=1e-5,
                                   rtol=1e-5)


# ---- (b) B and C a group of heads -----------------------------------------


def _draw(S, G, nh_=8, B=2, hd=4, N=16):
    ks = jax.random.split(jax.random.PRNGKey(S + G), 6)
    xs = jax.random.normal(ks[0], (B, S, nh_, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh_)) - 2.0)
    dt = dt.at[1, S - 3:].set(0.0)  # rows that are not live freeze the state
    A = -jnp.exp(jax.random.uniform(ks[2], (nh_,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(ks[3], (B, S, G, N))
    Cm = jax.random.normal(ks[4], (B, S, G, N))
    S0 = jax.random.normal(ks[5], (B, nh_, hd, N))
    return xs, dt, A, Bm, Cm, S0


@pytest.mark.parametrize("S,chunk,G", [(37, 8, 4), (5, 8, 2), (16, 8, 4),
                                       (23, 256, 2)])
def test_grouped_scan_is_the_recurrence_with_each_heads_own_group(S, chunk,
                                                                  G):
    """B/C a group through the scan and through the step against the form
    the ops had: B and C written out a head (``jnp.repeat``)."""
    xs, dt, A, Bm, Cm, S0 = _draw(S, G)
    rep = lambda a: jnp.repeat(a, 8 // G, axis=2)  # head h reads h // (8/G)
    y, state = ssm_scan(xs, dt, A, Bm, Cm, S0, chunk)
    y_h, state_h = ssm_scan(xs, dt, A, rep(Bm), rep(Cm), S0, chunk)
    np.testing.assert_allclose(y, y_h, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(state, state_h, atol=2e-5, rtol=2e-5)
    want, s = [], S0
    for t in range(S):
        y_t, s = ssm_step(xs[:, t:t + 1], dt[:, t:t + 1], A, Bm[:, t:t + 1],
                          Cm[:, t:t + 1], s)
        y_r, s_r = ssm_step(xs[:, t:t + 1], dt[:, t:t + 1], A,
                            rep(Bm)[:, t:t + 1], rep(Cm)[:, t:t + 1],
                            s if t else S0)
        if t == 0:
            np.testing.assert_allclose(y_t, y_r, atol=1e-6, rtol=1e-6)
            np.testing.assert_allclose(s, s_r, atol=1e-6, rtol=1e-6)
        want.append(y_t)
        if t == S - 4:
            frozen = s[1]
    np.testing.assert_allclose(y, jnp.concatenate(want, axis=1), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(state, s, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(frozen))


def test_the_gated_norm_is_a_groups_own():
    """The mixer alone against the reference's: B and C a group, the mean
    square over each group's channels."""
    cfg, _, params = make_engine()
    m = cfg.model
    lp = jax.tree.map(lambda v: v[0], {
        n: v for n, v in params["me_0"].items() if n not in ("w1", "w2")})
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 12, 64), jnp.float32)
    live = jnp.ones((1, 12), bool)
    conv = jnp.zeros((1, 3, nh.conv_width(m)))
    ssm = jnp.zeros((1, 8, 16, 16))
    got, _, _ = nh.mamba_mixer(lp, x, conv, ssm, live, m, one_step=False)
    want = ref._mamba(x[0], lp["in_proj"], lp["conv_w"], lp["conv_b"],
                      lp["dt_bias"], lp["A_log"], lp["D"], lp["gate_norm"],
                      lp["out_proj"], heads=8, d_head=16, d_state=16,
                      groups=4, eps=1e-5)
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # normalised over all of d_inner (``granite_hybrid``'s rule) it differs
    y = jax.random.normal(jax.random.PRNGKey(3), (12, 4, 32)) \
        * jnp.asarray([1.0, 2.0, 4.0, 8.0])[None, :, None]
    own = ref._rms_norm(y, jnp.ones((4, 32)), 1e-5).reshape(12, 128)
    flat = ref._rms_norm(y.reshape(12, 128), jnp.ones((128,)), 1e-5)
    assert float(jnp.abs(own - flat).max()) > 0.5


# ---- (c) a state with no token axis ----------------------------------------


def test_pad_rows_leave_state_and_conv_tail_as_at_length():
    _, padded, params = make_engine(prefill_chunk=64)  # 21 -> bucket 32
    _, exact, _ = make_engine(prefill_chunk=64, min_prefill_bucket=21)
    prompt = PROMPT[:21]
    kv_p, last_p = padded.prefill(params, prompt)
    kv_e, last_e = exact.prefill(params, prompt)
    assert kv_p["ssm"].shape == (N_M, 1, 8, 16, 16)
    assert kv_p["ssm"].dtype == jnp.float32
    assert kv_p["conv"].shape == (N_M, 1, 3, 128 + 2 * 4 * 16)
    assert kv_p["k"].shape == (1, 1, 32, 2, 16)
    close = dict(atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(kv_p["ssm"], kv_e["ssm"], **close)
    np.testing.assert_allclose(kv_p["conv"], kv_e["conv"], **close)
    np.testing.assert_allclose(last_p, last_e, **close)
    _, chunks, _ = make_engine(prefill_chunk=16)  # 21 = 16 + 5 of 16
    cache, _ = chunks.prefill_chunked(params, chunks.init_cache(), prompt, 1)
    np.testing.assert_allclose(cache["ssm"][:, 1], kv_e["ssm"][:, 0], **close)
    np.testing.assert_allclose(cache["conv"][:, 1], kv_e["conv"][:, 0],
                               **close)
    assert not np.asarray(cache["ssm"][:, 0]).any()  # the other slot


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_slot_used_twice_forgets_its_first_occupant(chunk):
    _, engine, params = make_engine(prefill_chunk=chunk)
    _, _, cache = program_logits(engine, params, PROMPT)
    assert np.abs(np.asarray(cache["ssm"][:, 0])).max() > 0
    cache = engine.release(cache, 0)
    seq, got, _ = program_logits(engine, params, OTHER, cache=cache)
    assert worst_rel_err(got, reference_rows(params, seq, len(OTHER))) < 1e-3


def test_a_parked_slot_is_bit_equal_and_uncounted_in_a_decode_block():
    _, engine, params = make_engine()
    cache, last0 = admit(engine, params, engine.init_cache(), PROMPT, 0)
    cache, last1 = admit(engine, params, cache, OTHER[:30], 1)
    before = {n: np.asarray(cache[n][:, 1]) for n in ("ssm", "conv")}
    moved = np.asarray(cache["ssm"][:, 0])
    engine.take_stats()
    keys = np.stack([np.asarray(jax.random.PRNGKey(i))
                     for i in range(engine.decode_block_len)])
    toks = np.asarray([np.argmax(last0), np.argmax(last1)], np.int32)
    r = engine.decode_block(
        params, cache, toks, keys, -np.ones(2, np.int32),
        np.array([3, 0], np.int32), np.zeros(2, np.float32),
        np.zeros(2, np.int32), np.ones(2, np.float32))
    assert list(np.asarray(r.counts)) == [3, 0]
    for n in ("ssm", "conv"):  # slot 1 is parked and out of budget
        np.testing.assert_array_equal(np.asarray(r.cache[n][:, 1]),
                                      before[n])
    assert np.abs(np.asarray(r.cache["ssm"][:, 0]) - moved).max() > 0
    stats = dict(zip(nh.STAT_NAMES, engine.take_stats()))
    # 8 steps x 3 Mamba layers ran; slot 0 advanced in 3 of the steps
    assert stats["ssm_layer_steps"] == 8 * N_M
    assert stats["ssm_state_updates"] == 3 * N_M
    # 8 steps x 3 expert layers; the one live row's held assignments only
    assert stats["moe_layer_steps"] == 8 * N_E
    assert 0 < stats["moe_assignments"] <= 2 * 3 * N_E
    assert stats["moe_experts_hit"] <= stats["moe_assignments"]
    # 2 rows pad to a sublane tile of 8 through the pass (float32), 3 held
    assert stats["moe_expert_rows"] == 8 * N_E * 2 * 3
    # slot 1 decodes on from where it stood, as the reference has it
    seq = OTHER[:30] + [int(toks[1])]
    _, logits = decode(engine, params, r.cache, seq[-1], 1)
    assert worst_rel_err([logits], reference_rows(params, seq, 31)) < 1e-3


@pytest.mark.parametrize("rounded", [False, True])
def test_the_state_of_a_bfloat16_model_is_float32_all_the_way(monkeypatch,
                                                              rounded):
    """The serving check's logits cannot tell a state kept in bfloat16 from
    the float32 the configuration states (``control_nemotron.py``'s
    ``state_bf16`` reads what the sound program reads). This can: after a
    chunked admission and decode steps of a bfloat16 model next to none of
    the state's entries are ones bfloat16 holds exactly; rounded anywhere on
    its way, all are."""
    if rounded:
        mixer = nh.mamba_mixer

        def rounding(*args, **kw):
            out, conv_out, ssm_out = mixer(*args, **kw)
            return out, conv_out, jax.lax.reduce_precision(
                ssm_out, exponent_bits=8, mantissa_bits=7)

        monkeypatch.setattr(nh, "mamba_mixer", rounding)
    _, engine, params = make_engine({"dtype": "bfloat16"}, fresh=True)
    _, _, cache = program_logits(engine, params, PROMPT)  # 3 chunks, 4 steps
    state = cache["ssm"][:, 0]
    assert state.dtype == jnp.float32 and cache["conv"].dtype == jnp.bfloat16
    there = state != 0
    exact = state.astype(jnp.bfloat16).astype(jnp.float32) == state
    share = float(jnp.sum(exact & there) / jnp.sum(there))
    assert share == 1.0 if rounded else share < 0.01, share


def test_the_window_is_held_to_whole_chunks():
    assert nh.CARRIES_STATE
    with pytest.raises(ValueError, match="multiple of prefill_chunk"):
        InferenceEngine(make_config(), slots=2, max_seq_len=120,
                        prefill_chunk=16)


# ---- (d) the shares add up to the uncut layer ------------------------------


def test_the_four_shares_add_up_to_the_uncut_expert_layer():
    """Each rank's routed part goes through ``W_up`` by itself (linear), the
    shared expert is what every chip computes alike and is counted once."""
    cut = dict(TOY, n_routed_experts=2, ep_size=4, num_experts_per_tok=3)
    uncut = dict(cut, n_routed_experts=8, ep_size=1, ep_rank=0)
    m_full = make_config(uncut).model
    full = jax.jit(lambda k: nh.init_params(k, m_full))(
        jax.random.PRNGKey(11))
    lp = jax.tree.map(lambda v: v[0], full["me_0"])
    # rows of unit mean square: the sublayer's own norm (weight 1) leaves
    # them as they are, and the reference's experts take them normed
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    x = ref._rms_norm(x, 1.0, 0.0)
    want = np.asarray(ref.experts(lp, x[0], uncut))
    shared = np.asarray(ref._relu2(x[0], lp["ws_up"], lp["ws_down"]))
    live = jnp.ones((1, 24), bool)
    cfg_of = lambda rank: make_config(dict(cut, ep_rank=rank))
    total, held = shared.copy(), 0
    for rank in range(4):
        part = {**lp, **{n: lp[n][2 * rank:2 * rank + 2]
                         for n in ("w1", "w2")}}
        y, _, counted = nh.expert_sublayer(
            part, x, cfg_of(rank), None, {}, None, False, None, live)
        total += np.asarray(y[0]) - shared
        held += int(counted[0])
    np.testing.assert_allclose(total, want, atol=1e-4 * np.abs(want).max())
    assert held == 24 * 3  # every token's experts are held by some rank


def test_the_sublayers_norm_is_taken_before_the_experts():
    """``expert_sublayer`` on ``h`` is ``ref.experts`` on ``RMSNorm(h)``."""
    cfg, _, params = make_engine()
    lp = jax.tree.map(lambda v: v[0], params["me_0"])
    h = 3.0 * jax.random.normal(jax.random.PRNGKey(9), (1, 10, 64))
    y, _, counted = nh.expert_sublayer(lp, h, cfg, None, {}, None, False,
                                       None, jnp.ones((1, 10), bool))
    x = ref._rms_norm(h[0], lp["e_norm"], 1e-5)
    np.testing.assert_allclose(y[0], ref.experts(lp, x, dict(TOY)),
                               atol=2e-5)
    assert len(counted) == len(nh.STAT_NAMES)


def test_router_is_sigmoid_with_a_bias_on_the_choice_alone():
    scores = jnp.asarray([[0.5, 0.9, 0.9, 0.1, 0.25, 0.9]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 1.0, 0.0, 0.0])
    chosen, w = experts.route(scores, bias, k=3, scale=5.0, eps=1e-20)
    assert chosen.tolist() == [[3, 1, 2]]  # ties to the lower index
    np.testing.assert_allclose(w[0], 5.0 * np.array([0.1, 0.9, 0.9]) / 1.9,
                               rtol=1e-6)
    logit = lambda p: jnp.log(p / (1 - p))
    r_chosen, r_w = ref._route(logit(scores), jnp.eye(6), bias, k=3,
                               scale=5.0)
    assert r_chosen.tolist() == chosen.tolist()
    np.testing.assert_allclose(r_w, w, rtol=1e-5)


# ---- (e) the expert share's second form through its three orders ----------


def _relu2_case(N, dtype=jnp.float32, held=5, L=32, I=64, layers=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + N), 4)
    lp = {"w1": jax.random.normal(ks[0], (layers, held, L, I), dtype) * 0.2,
          "w2": jax.random.normal(ks[1], (layers, held, I, L), dtype) * 0.2,
          "row": jnp.asarray(1, jnp.int32)}
    x = jax.random.normal(ks[2], (N, L), dtype)
    rng = np.random.default_rng(N)
    w = rng.uniform(0.05, 1.0, (N, held)) * (rng.uniform(0, 1, (N, held))
                                             < 0.4)
    w[:, 1] = 0.0  # a held expert no row chose
    return lp, x, jnp.asarray(w, jnp.float32)


def _routed(x, w_held, lp):
    """``routed_experts`` traced afresh: the rules are read at trace time."""
    return jax.jit(lambda *a: experts.routed_experts(*a))(x, w_held, lp)


def _relu2_reference(lp, x, w_held):
    f = lambda v: jnp.asarray(v, jnp.float32)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_held.shape[1]):
        w1, w2 = f(lp["w1"][1, e]), f(lp["w2"][1, e])
        y += (jnp.square(jax.nn.relu(f(x) @ w1)) @ w2) * w_held[:, e:e + 1]
    return np.asarray(y)


@pytest.mark.parametrize("N", [8, 64, 100, 200, 400, 1100])
def test_relu2_experts_are_equal_through_the_three_orders(N, monkeypatch):
    """The loop, the pipelined pass (below the ridge) and the grouped call
    (from it up) on two-matrix experts: the rule picks by rows as for the
    gated form, and each agrees with the loop and with plain jax.numpy."""
    lp, x, w_held = _relu2_case(N)
    assert experts.expert_leaves(lp) == ["w1", "w2"]
    want = _relu2_reference(lp, x, w_held)
    got, run, pipelined = _routed(x, w_held, lp)
    monkeypatch.setattr(experts, "takes_grouped", lambda rows: False)
    monkeypatch.setattr(experts, "takes_pipelined", lambda *a: False)
    loop, run_loop, _ = _routed(x, w_held, lp)
    np.testing.assert_allclose(loop, want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(got, loop, atol=1e-4, rtol=1e-4)
    assert int(run_loop) == N * 5
    if N >= experts.RIDGE_ROWS:
        assert int(pipelined) == 0 and int(run) % grouped.TILE == 0
        assert int(run) < N * 5 + 5 * grouped.TILE * -(-N // 512)
    else:
        assert int(pipelined) == 1 and int(run) == N * 5


def test_relu2_experts_in_bfloat16_round_where_the_loop_rounds(monkeypatch):
    lp, x, w_held = _relu2_case(64, jnp.bfloat16)
    got, _, _ = _routed(x, w_held, lp)
    monkeypatch.setattr(experts, "takes_pipelined", lambda *a: False)
    loop, _, _ = _routed(x, w_held, lp)
    scale = float(jnp.max(jnp.abs(loop)))
    np.testing.assert_allclose(got, loop, atol=2e-2 * scale)
    lp, x, w_held = _relu2_case(384, jnp.bfloat16)
    got, _, _ = _routed(x, w_held, lp)
    monkeypatch.setattr(experts, "takes_grouped", lambda rows: False)
    loop, _, _ = _routed(x, w_held, lp)
    np.testing.assert_allclose(got, loop,
                               atol=2e-2 * float(jnp.max(jnp.abs(loop))))


def test_the_share_feeds_the_routed_and_the_shared_expert_apart():
    """``routed_in``/``routed_out``: the routed experts see the latent, the
    shared expert the stream, and the sum is the reference's."""
    cfg, _, params = make_engine()
    lp = jax.tree.map(lambda v: v[0], params["me_0"])
    x = jax.random.normal(jax.random.PRNGKey(4), (10, 64), jnp.float32)
    w_held = jnp.asarray(np.random.default_rng(1).uniform(0, 1, (10, 3))
                         * (np.arange(30).reshape(10, 3) % 2), jnp.float32)
    y, counted = experts.share(lp, x, w_held, routed_in=x @ lp["latent_down"],
                               routed_out=lambda r: r @ lp["latent_up"])
    latent = x @ lp["latent_down"]
    want = sum(w_held[:, e:e + 1] * experts.relu2(latent, lp["w1"][e],
                                                  lp["w2"][e])
               for e in range(3)) @ lp["latent_up"] \
        + experts.relu2(x, lp["ws_up"], lp["ws_down"])
    np.testing.assert_allclose(y, want, atol=2e-5)
    assert int(counted[0]) == int(jnp.sum(w_held > 0))
    assert experts.expert(x, lp["ws_up"], lp["ws_down"]).shape == x.shape


# ---- (f) the pattern of layers ---------------------------------------------


def test_stacking_gives_the_published_order():
    """The whole 88-letter pattern: the stretches laid end to end are the
    pattern, every unit holds a kind at most once, and each group knows
    where its units and its kinds' rows begin."""
    stretches = nh.stacking(PUBLISHED)
    assert "".join(u * r for u, _, r in stretches) == PUBLISHED
    assert all(len(set(u)) == len(u) for u, _, _ in stretches)
    assert [f for _, f, _ in stretches] == list(np.cumsum(
        [0] + [len(u) * r for u, _, r in stretches])[:-1])
    assert nh.stacking("EMEMEMEMEM*") == [("EM", 0, 5), ("*", 10, 1)]
    assert PUBLISHED[26:37] == "EMEMEMEMEM*"  # the cell's period
    m = make_config(dict(num_hidden_layers=88,
                         hybrid_override_pattern=PUBLISHED)).model
    groups = nh.layer_groups(m)
    assert len(groups) == len(stretches) == 17
    order, rows = [], {k: 0 for k in nh.KINDS}
    for (name, fn, n), (unit, _, r) in zip(groups, stretches):
        kw = fn.keywords
        assert n == r and kw["unit"] == unit and kw["first"] == len(order)
        assert kw["kind_first"] == rows
        assert name.split("_")[0] == "".join(nh.TAG[k] for k in unit)
        for i in range(n):
            order.append(unit)
        for k in unit:
            rows[k] += n
    assert "".join(order) == PUBLISHED
    assert rows == {"M": 40, "E": 40, "*": 8} == nh.kind_counts(m)
    cache = jax.eval_shape(lambda: nh.init_cache(m, 2, 64))
    assert cache["ssm"].shape[:2] == (40, 2) and cache["k"].shape[:2] == (8, 2)


def test_the_tree_and_the_cache_of_the_toy():
    cfg, engine, params = make_engine()
    assert model_module(cfg.model) is nh
    assert [(n, c) for n, _, c in nh.layer_groups(cfg.model)] == \
        [("me_0", 2), ("mae_1", 1)]
    g = params["me_0"]
    assert g["w1"].shape == (2, 3, 32, 32) and "w3" not in g
    assert g["latent_down"].shape == (2, 64, 32)
    assert g["router"].shape == (2, 64, 6) and g["router_bias"].dtype \
        == jnp.float32
    assert g["in_proj"].shape == (2, 64, 128 + 128 + 2 * 4 * 16 + 8)
    assert set(params["mae_1"]) >= {"wq", "in_proj", "ws_up", "a_norm"}
    cache = engine.init_cache()
    assert cache["k"].shape == cache["v"].shape == (1, 2, 128, 2, 16)
    assert cache["ssm"].shape == (N_M, 2, 8, 16, 16)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["conv"].shape == (N_M, 2, 3, 256)
    assert nh.num_params(cfg.model) == sum(
        v.size for v in jax.tree.leaves(params))
    A = np.exp(np.asarray(g["A_log"]))
    assert 1.0 <= A.min() and A.max() <= 16.0 and (np.asarray(g["D"]) == 1
                                                   ).all()


def test_stats_leave_the_programs_a_row_a_unit():
    _, engine, params = make_engine(prefill_chunk=64)
    engine.prefill(params, PROMPT)
    pending, = engine._stats_pending
    assert pending.shape == (3, len(nh.STAT_NAMES))
    rows = dict(zip(nh.STAT_NAMES, np.asarray(pending).T))
    assert list(rows["moe_layer_steps"]) == [1, 1, 1]  # an E a unit
    assert list(rows["ssm_tokens_scanned"]) == [44, 44, 44]
    assert not rows["ssm_state_updates"].any()
    # the bucket's 64 rows through the three held experts' pass
    assert list(rows["moe_expert_rows"]) == [64 * 3] * 3
    assert list(rows["moe_pipelined_steps"]) == [1, 1, 1]


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
    for name in nh.STAT_NAMES:
        line, = [l for l in text.splitlines()
                 if l.startswith(f"picotron_{name}_total ")]
        got[name] = float(line.split()[1])
    assert got["ssm_tokens_scanned"] == N_M * (44 + 9 + 20)
    assert got["ssm_state_updates"] == N_M * 3 * 4
    assert got["moe_layer_steps"] > 0 and got["moe_assignments"] > 0
    _, fresh, _ = make_engine()
    alone = ContinuousBatcher(fresh, params, seed=0).run(
        [Request(uid="x", prompt=OTHER[:20], max_new_tokens=5)])
    assert out["r2"].tokens == alone["x"].tokens


# ---- (g) what is refused, by name ------------------------------------------


@pytest.mark.parametrize("model,match", [
    ({"hybrid_override_pattern": "MEMEM*"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "MEMEM*-"}, "dense MLP"),
    ({"hybrid_override_pattern": "MEMEMEM"}, "at least one"),
    ({"num_nextn_predict_layers": 1}, "multi-token-prediction"),
    ({"n_groups": 3}, "n_groups"),
    ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    ({"use_conv_bias": False}, "use_conv_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"n_shared_experts": 2}, "n_shared_experts"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"moe_latent_size": 0}, "moe_latent_size"),
    ({"ep_rank": 2}, "ep_rank"),
    ({"num_experts_per_tok": 7}, "num_experts_per_tok"),
    ({"n_group": 4}, "n_group"),
    ({"model_type": "nemotron"}, "unknown model_type"),
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
    size (by shapes) counts what ``opcount_nemotron.num_params`` counts."""
    import sys
    sys.path.insert(0, ROOT)
    from benchmarks import common, opcount_nemotron

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-3-super-ep4-l11.json")) as f:
        config = json.load(f)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"]) \
        == (4096, 32, 2, 128)
    assert (config["mamba_num_heads"], config["mamba_head_dim"],
            config["ssm_state_size"], config["n_groups"],
            config["conv_kernel"]) == (128, 64, 128, 8, 4)
    assert (config["moe_intermediate_size"], config["moe_latent_size"],
            config["moe_shared_expert_intermediate_size"],
            config["num_experts_per_tok"],
            config["n_routed_experts"] * config["ep_size"]) \
        == (2688, 1024, 5376, 22, 512)
    assert config["hybrid_override_pattern"] == PUBLISHED[26:37]
    assert config["rms_norm_eps"] == config["layer_norm_epsilon"]
    m = common.model_section(config)
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True}, "model": m,
        "training": {"seq_length": 8192}, "dataset": {"name": "synthetic"}})
    n = nh.num_params(cfg.model)
    assert n == opcount_nemotron.num_params(config)
    assert 4.6e9 < n < 4.7e9
    # a program without the block: the first key ModelConfig lacks, exit 2
    config["model_keys"] = config["model_keys"] + ["mamba_d_mystery"]
    config["mamba_d_mystery"] = 1
    with pytest.raises(SystemExit) as e:
        common.model_section(config)
    assert e.value.code == 2
