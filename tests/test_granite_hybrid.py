"""The Granite-4.0-H block (models/granite_hybrid.py) on the serving path, at
toy size in float32 on the CPU with seeded weights, against the plain
reference (benchmarks/reference/granite_hybrid.py): the engine's programs
through a cache of three kinds of leaf, what a state with no token axis asks
of them (pad rows, a slot's second occupant, a slot that is not live, the
state carried from chunk to chunk), the chunked scan against the recurrence,
the expert share, and what ``Config.validate`` refuses."""

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
from engine_memo import memoized

from picotron_tpu.inference import InferenceEngine
from picotron_tpu.models import granite_hybrid as gh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "granite-4.0-h-small-ep2-l10.serve-chat-closed"
PUBLISHED_TYPES = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4
                   + (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 3)

TOY = block_toys.TOYS["granitemoehybrid"]


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_granite_hybrid",
        os.path.join(ROOT, "benchmarks", "reference", "granite_hybrid.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


make_config = partial(block_toys.make_config, "granitemoehybrid")


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, slots=2, max_seq_len=128,
                             **{"prefill_chunk": 16, **kw})
    params = jax.jit(lambda k: gh.init_params(k, cfg.model))(
        jax.random.PRNGKey(7))
    return cfg, engine, engine.shard_params(params)


def admit(engine, params, cache, prompt, slot=0):
    """The batcher's admission: chunks past ``prefill_chunk``, else the
    one-shot program and an insert. (cache, the last position's logits)."""
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
    """The runner's check (benchmarks/runners/serve.py::program_logits):
    the prompt through prefill, then ``steps`` greedy decode steps through
    the cache; (sequence, logits rows, cache)."""
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


RNG = np.random.default_rng(3)
PROMPT = [int(t) for t in RNG.integers(1, 256, 44)]
OTHER = [int(t) for t in RNG.integers(1, 256, 44)]


# ---- (a) the engine's programs against the reference ----------------------


@pytest.mark.parametrize("n_prompt,chunk", [
    (44, 16),   # three chunks: the state is carried twice, then decoded from
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
    stats = dict(zip(gh.STAT_NAMES, engine.take_stats()))
    # four Mamba layers: every prompt token scanned once a layer, every
    # decode step advances the one live slot in each
    assert stats["ssm_tokens_scanned"] == 4 * n_prompt
    assert stats["ssm_state_updates"] == stats["ssm_layer_steps"] == 4 * 4
    # 2 of a router 6 wide a token and layer; this rank holds 3
    assert 0 < stats["moe_assignments"] <= 2 * 5 * (n_prompt + 4)


def test_the_whole_forward_matches_the_reference_at_every_position():
    """The one-shot program's stream, read out at every row: the mixers and
    the experts as the reference has them, not only at the last position."""
    cfg, engine, params = make_engine(prefill_chunk=64)
    m = cfg.model
    tokens = jnp.asarray([PROMPT])

    def forward(params, tokens):
        h = engine._embed(params, tokens)
        live = jnp.ones(tokens.shape, bool)
        h, _, _ = engine._prefill_groups(params, h, engine._cos,
                                         engine._sin, live)
        return gh.head_logits(params, h, cfg)

    from picotron_tpu.utils import shard_map
    from jax.sharding import PartitionSpec as P
    got = jax.jit(shard_map(forward, engine.topo.mesh,
                            in_specs=(engine._pspecs, P()),
                            out_specs=P()))(params, tokens)
    want = ref.forward_logits(params, np.asarray([PROMPT]), dict(TOY))
    assert m.tie_word_embeddings and "lm_head" not in params
    np.testing.assert_allclose(np.asarray(got), want,
                               atol=1e-3 * np.abs(want).max())


def test_a_chunk_boundary_changes_nothing():
    _, e_chunks, params = make_engine(prefill_chunk=16)
    _, e_whole, _ = make_engine(prefill_chunk=64)
    _, a, ca = program_logits(e_chunks, params, PROMPT)
    _, b, cb = program_logits(e_whole, params, PROMPT)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=1e-6)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(ca[name][:, 0], cb[name][:, 0], atol=1e-5,
                                   rtol=1e-5)
    sa, sb = (dict(zip(gh.STAT_NAMES, e.take_stats()))
              for e in (e_chunks, e_whole))
    # the same assignments and scans whatever the number of programs (and so
    # of expert-layer steps, and of padded rows through the experts' loop)
    for name in ("moe_layer_steps", "moe_experts_hit", "moe_expert_rows",
                 "moe_pipelined_steps"):
        sa.pop(name), sb.pop(name)
    assert sa == sb


# ---- (b) the chunked scan against the recurrence ---------------------------


@pytest.mark.parametrize("S,chunk", [(37, 8), (5, 8), (16, 8), (23, 256)])
def test_chunked_scan_is_the_sequential_recurrence(S, chunk):
    ks = jax.random.split(jax.random.PRNGKey(S), 6)
    B, nh, hd, N = 2, 4, 8, 16
    xs = jax.random.normal(ks[0], (B, S, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)) - 2.0)
    dt = dt.at[1, S - 3:].set(0.0)  # rows that are not live freeze the state
    A = -jnp.exp(jax.random.uniform(ks[2], (nh,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    S0 = jax.random.normal(ks[5], (B, nh, hd, N))
    y, state = gh.ssm_scan(xs, dt, A, Bm, Cm, S0, chunk)
    want, s = [], S0
    for t in range(S):
        y_t, s = gh.ssm_step(xs[:, t:t + 1], dt[:, t:t + 1], A,
                             Bm[:, t:t + 1], Cm[:, t:t + 1], s)
        want.append(y_t)
        if t == S - 4:
            frozen = s[1]
    np.testing.assert_allclose(y, jnp.concatenate(want, axis=1), atol=2e-5,
                               rtol=2e-5)
    np.testing.assert_allclose(state, s, atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(frozen))


# ---- (c) a state with no token axis ----------------------------------------


def test_pad_rows_leave_state_and_conv_tail_as_at_length():
    """A bucket's pad rows: the state and the conv's last inputs a one-shot
    prefill returns are those behind the last real token."""
    _, padded, params = make_engine(prefill_chunk=64)  # 21 -> bucket 32
    _, exact, _ = make_engine(prefill_chunk=64, min_prefill_bucket=21)
    prompt = PROMPT[:21]
    assert padded.prefill_bucket(21) == 32 and exact.prefill_bucket(21) == 21
    kv_p, last_p = padded.prefill(params, prompt)
    kv_e, last_e = exact.prefill(params, prompt)
    assert kv_p["ssm"].shape == (4, 1, 8, 16, 16)
    assert kv_p["ssm"].dtype == jnp.float32
    assert kv_p["conv"].shape == (4, 1, 3, 16 * 8 + 2 * 16)
    assert kv_p["k"].shape == (1, 1, 32, 2, 16)
    close = dict(atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(kv_p["ssm"], kv_e["ssm"], **close)
    np.testing.assert_allclose(kv_p["conv"], kv_e["conv"], **close)
    np.testing.assert_allclose(last_p, last_e, **close)
    # and a chunk's pad rows: 21 = 16 + 5 of 16
    _, chunks, _ = make_engine(prefill_chunk=16)
    cache, _ = chunks.prefill_chunked(params, chunks.init_cache(), prompt, 1)
    np.testing.assert_allclose(cache["ssm"][:, 1], kv_e["ssm"][:, 0], **close)
    np.testing.assert_allclose(cache["conv"][:, 1], kv_e["conv"][:, 0],
                               **close)
    assert not np.asarray(cache["ssm"][:, 0]).any()  # the other slot: untouched


@pytest.mark.parametrize("chunk", [16, 64])
def test_a_slot_used_twice_forgets_its_first_occupant(chunk):
    """``release`` only zeroes a length; the second request's admission
    replaces the state (one-shot: the insert; chunked: the first chunk
    starts from zeros), and its logits are the reference's."""
    _, engine, params = make_engine(prefill_chunk=chunk)
    _, _, cache = program_logits(engine, params, PROMPT)
    assert np.abs(np.asarray(cache["ssm"][:, 0])).max() > 0
    cache = engine.release(cache, 0)
    seq, got, _ = program_logits(engine, params, OTHER, cache=cache)
    want = reference_rows(params, seq, len(OTHER))
    assert worst_rel_err(got, want) < 1e-3


def test_a_slot_that_is_not_live_does_not_advance_in_a_decode_block():
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
    assert list(np.asarray(r.cache["lengths"])) == [len(PROMPT) + 3, 30]
    for n in ("ssm", "conv"):  # slot 1 is parked and out of budget
        np.testing.assert_array_equal(np.asarray(r.cache[n][:, 1]),
                                      before[n])
    assert np.abs(np.asarray(r.cache["ssm"][:, 0]) - moved).max() > 0
    stats = dict(zip(gh.STAT_NAMES, engine.take_stats()))
    # 8 steps x 4 Mamba layers ran; slot 0 advanced in 3 of the steps
    assert stats["ssm_layer_steps"] == 8 * 4
    assert stats["ssm_state_updates"] == 3 * 4
    # slot 1 decodes on from where it stood, as the reference has it
    seq = OTHER[:30] + [int(toks[1])]
    _, logits = decode(engine, params, r.cache, seq[-1], 1)
    want = reference_rows(params, seq, len(seq))
    assert worst_rel_err([logits], want) < 1e-3


@pytest.mark.parametrize("rounded", [False, True])
def test_the_state_of_a_bfloat16_model_is_float32_all_the_way(monkeypatch,
                                                              rounded):
    """The configuration states float32 for the recurrent state, and the
    serving check's logits cannot tell a state kept in bfloat16 from it
    (every activation beside it is rounded too: PERF.md section 7). This
    can: after a chunked admission and decode steps of a bfloat16 model the
    slot's state is float32 and next to none of its entries are ones
    bfloat16 holds exactly; rounded anywhere on its way
    (``benchmarks/tests/control_granite.py``'s ``state_bf16``), all are."""
    if rounded:
        mixer = gh.mamba_mixer

        def rounding(*args, **kw):
            out, conv_out, ssm_out = mixer(*args, **kw)
            return out, conv_out, jax.lax.reduce_precision(
                ssm_out, exponent_bits=8, mantissa_bits=7)

        monkeypatch.setattr(gh, "mamba_mixer", rounding)
    _, engine, params = make_engine({"dtype": "bfloat16"}, fresh=True)
    _, _, cache = program_logits(engine, params, PROMPT)  # 3 chunks, 4 steps
    state = cache["ssm"][:, 0]
    assert state.dtype == jnp.float32 and cache["conv"].dtype == jnp.bfloat16
    there = state != 0
    exact = state.astype(jnp.bfloat16).astype(jnp.float32) == state
    share = float(jnp.sum(exact & there) / jnp.sum(there))
    assert share == 1.0 if rounded else share < 0.01, share


def test_the_window_is_held_to_whole_chunks():
    """``prefill_chunked`` slides a last chunk that would pass the window
    back inside it and re-feeds the overlap: a state cannot take that."""
    with pytest.raises(ValueError, match="multiple of prefill_chunk"):
        InferenceEngine(make_config(), slots=2, max_seq_len=120,
                        prefill_chunk=16)
    _, engine, params = make_engine()  # 128 = 8 x 16: the last chunk fits
    prompt = (PROMPT * 3)[:124]
    seq, got, _ = program_logits(engine, params, prompt, steps=2)
    assert worst_rel_err(got, reference_rows(params, seq, 124)) < 1e-3


# ---- (d) the share adds up to the uncut layer ------------------------------


def test_the_shares_add_up_to_the_uncut_expert_layer():
    uncut = dict(TOY, num_local_experts=6, ep_size=1, ep_rank=0)
    m_full = make_config(uncut).model
    full = jax.jit(lambda k: gh.init_params(k, m_full))(
        jax.random.PRNGKey(11))
    lp = jax.tree.map(lambda v: v[0], full["mamba_0"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    want = np.asarray(ref.experts(lp, x[0], uncut))
    shared = np.asarray(ref._swiglu(x[0], lp["ws_gate"], lp["ws_up"],
                                    lp["ws_down"]))
    live = jnp.ones((1, 24), bool)
    total = shared.copy()  # what both chips compute alike, counted once
    held = 0
    for rank in range(2):
        m = make_config(dict(TOY, ep_rank=rank)).model
        part = {**lp, **{n: lp[n][3 * rank:3 * rank + 3]
                         for n in ("w1", "w3", "w2")}}
        y, (assigned, *_) = gh.expert_mlp(part, x, m, live)
        total += np.asarray(y[0]) - shared
        held += int(assigned)
    np.testing.assert_allclose(total, want, atol=2e-5)
    assert held == 24 * 2  # every token's experts are held by some rank


def test_router_takes_the_largest_logits_and_softmaxes_those():
    logits = jnp.asarray([[0.5, 2.0, 2.0, -1.0, 0.25, 2.0]])
    experts, w = gh.route(logits, 2)
    assert experts.tolist() == [[1, 2]]  # ties to the lower index
    np.testing.assert_allclose(w, [[0.5, 0.5]], rtol=1e-6)
    experts, w = gh.route(logits, 4)
    assert experts.tolist() == [[1, 2, 5, 0]]
    e = np.exp(np.array([2.0, 2.0, 2.0, 0.5]))
    np.testing.assert_allclose(w[0], e / e.sum(), rtol=1e-6)
    r_experts, r_w = ref._route(jnp.eye(6)[:1] * 0 + logits, jnp.eye(6), k=4)
    assert r_experts.tolist() == experts.tolist()
    np.testing.assert_allclose(r_w, w, rtol=1e-6)


# ---- (e) the pattern of layers ---------------------------------------------


def test_layer_groups_are_the_runs_of_layer_types():
    m = make_config(dict(num_hidden_layers=40,
                         layer_types=PUBLISHED_TYPES)).model
    groups = gh.layer_groups(m)
    assert [n for _, _, n in groups] == [5, 1, 9, 1, 9, 1, 9, 1, 4]
    assert [g[0].split("_")[0] for g in groups] == \
        ["mamba", "attention"] * 4 + ["mamba"]
    assert sum(n for name, _, n in groups if name[0] == "m") == 36
    assert sum(n for name, _, n in groups if name[0] == "a") == 4
    # each run knows where it begins among all layers and among its kind
    assert [(g[1].keywords["first"], g[1].keywords["kind_first"])
            for g in groups] == [(0, 0), (5, 0), (6, 5), (15, 1), (16, 14),
                                 (25, 2), (26, 23), (35, 3), (36, 32)]
    cache = jax.eval_shape(lambda: gh.init_cache(m, 2, 64))
    assert cache["ssm"].shape[:2] == (36, 2)
    assert cache["k"].shape[:2] == (4, 2)


def test_the_cache_has_three_kinds_of_leaf():
    _, engine, _ = make_engine()
    cache = engine.init_cache()
    assert cache["k"].shape == cache["v"].shape == (1, 2, 128, 2, 16)
    assert cache["ssm"].shape == (4, 2, 8, 16, 16)
    assert cache["ssm"].dtype == jnp.float32
    assert cache["conv"].shape == (4, 2, 3, 160)
    assert engine.kv_cache_bytes == sum(
        a.size * a.dtype.itemsize for a in cache.values())


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
    ({"layer_types": ["mamba"] * 4}, "layer_types"),
    ({"layer_types": ["mamba", "mamba", "window", "mamba", "mamba"]},
     "layer_types"),
    ({"layer_types": ["mamba"] * 5}, "at least one"),
    ({"mamba_n_heads": 6}, "mamba_expand"),
    ({"mamba_n_groups": 2}, "mamba_n_groups"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"ep_rank": 2}, "ep_rank"),
    ({"num_experts_per_tok": 7}, "num_experts_per_tok"),
    ({"num_local_experts": 0}, "num_local_experts"),
    ({"model_type": "granitemoe"}, "unknown model_type"),
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
    with pytest.raises(ValueError, match="cache in the model's dtype"):
        InferenceEngine(make_config(), slots=2, max_seq_len=64,
                        cache_dtype="int8")


# ---- (g) the serving control, and the counters on /metrics -----------------


def test_bfloat16_fails_the_float32_check():
    """The program in the nearest precision below fails the 1e-3 the
    float32 engine is held to, read along the tokens the sound run chose."""
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


def test_stats_leave_the_programs_a_row_a_layer():
    _, engine, params = make_engine(prefill_chunk=64)
    engine.prefill(params, PROMPT)
    pending, = engine._stats_pending
    assert pending.shape == (5, len(gh.STAT_NAMES))
    assert pending.dtype == jnp.int32
    rows = dict(zip(gh.STAT_NAMES, np.asarray(pending).T))
    assert list(rows["moe_layer_steps"]) == [1] * 5  # behind every layer
    # the attention layer scans nothing
    assert list(rows["ssm_tokens_scanned"]) == [44, 44, 0, 44, 44]
    # a prefill is no decode step
    assert not rows["ssm_state_updates"].any()
    assert not rows["ssm_layer_steps"].any()
    # the bucket's 64 rows (44 live) through the three held experts' loop
    assert list(rows["moe_expert_rows"]) == [64 * 3] * 5


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
    for name in gh.STAT_NAMES:
        line, = [l for l in text.splitlines()
                 if l.startswith(f"picotron_{name}_total ")]
        got[name] = float(line.split()[1])
    assert got["ssm_tokens_scanned"] == 4 * (44 + 9 + 20)
    # each request's five tokens: one from its prefill, four decode steps
    assert got["ssm_state_updates"] == 4 * 3 * 4
    assert got["ssm_layer_steps"] >= got["ssm_state_updates"] / 2
    # three requests on two slots: the third took a slot a first one left,
    # and its stream is what it is alone in a fresh engine
    _, fresh, _ = make_engine()
    alone = ContinuousBatcher(fresh, params, seed=0).run(
        [Request(uid="x", prompt=OTHER[:20], max_new_tokens=5)])
    assert out["r2"].tokens == alone["x"].tokens


def test_seeded_draws_are_as_the_configuration_file_says():
    m = make_config().model
    p = jax.jit(lambda k: gh.init_params(k, m))(jax.random.PRNGKey(1))
    g = p["mamba_0"]
    assert g["A_log"].dtype == g["dt_bias"].dtype == g["D"].dtype \
        == jnp.float32
    A = np.exp(np.asarray(g["A_log"]))
    assert 1.0 <= A.min() and A.max() <= 16.0
    step = np.asarray(jax.nn.softplus(g["dt_bias"]))
    assert 0.99e-3 < step.min() and step.max() < 1.01e-1
    assert (np.asarray(g["D"]) == 1).all()
    Di, N = 128, 16
    bound = (1 / 64) ** 0.5
    top = np.abs(np.asarray(g["in_proj"])).max(axis=(0, 1))
    assert top[:2 * Di].max() <= bound and top[-8:].max() <= bound
    assert 0.9 * gh.BC_GAIN * bound < top[2 * Di:2 * Di + 2 * N].max() \
        <= gh.BC_GAIN * bound * 1.001
    # a unit-norm row of the embedding, once multiplied
    rows = np.linalg.norm(np.asarray(p["embed"]) * 12.0, axis=1)
    assert 0.7 < rows.mean() < 1.3
    assert gh.num_params(m) == sum(v.size for v in jax.tree.leaves(p))


# ---- (h) the cell's rehearsal ----------------------------------------------


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
    # tokens/s alone is bounded in this cell (its inter-token tail spreads
    # too widely), so the engine-wide readers it lists are the ``.tput``
    # ones, which move tokens/s (the device-trace ones need a chip)
    assert {"serve_out_tokens_per_s", "setup_s",
            "moe.held_assignments_per_step.granite",
            "ssm.state_updates_per_step",
            "ssm.prefill_scan_tokens_per_s",
            "engine.prefill_tflops.granite",
            "batcher.dispatch_gap_ms.tput",
            "batcher.deliver_ms.tput",
            # the parts of a round (ISSUE 37), from the window's scrapes
            "engine.issue_operands_ms.tput", "engine.issue_enqueue_ms.tput",
            "engine.sync_wait_ms.tput",
            "engine.sync_fetch_ms.tput",
            # the stall judge's counters (ISSUE 53): each prints at 0
            "batcher.stall_s", "engine.device_wait_stall_s",
            "front.oversleep_s"} == set(out["computed"])


def test_a_program_without_the_block_fails_the_cell_at_once(tmp_path):
    """What the parent does with the new cell: the first ``model_keys``
    name ``ModelConfig`` lacks ends the run with exit code 2 before any
    device work (here: a configuration that lists one more)."""
    sys.path.insert(0, ROOT)
    from benchmarks import common

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite-4.0-h-small-ep2-l10.json")) as f:
        config = json.load(f)
    m = common.model_section(config)
    assert m["model_type"] == "granitemoehybrid"
    assert m["layer_types"] == PUBLISHED_TYPES[:10]
    assert common.load_reference(config).__file__.endswith(
        "granite_hybrid.py")
    config["model_keys"] = config["model_keys"] + ["mamba_d_mystery"]
    config["mamba_d_mystery"] = 1
    with pytest.raises(SystemExit) as e:
        common.model_section(config)
    assert e.value.code == 2
