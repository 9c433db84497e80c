"""The Trinity block (models/afmoe.py) on the serving path, at toy size in
float32 on the CPU with seeded weights, against the plain reference
(benchmarks/reference/afmoe.py): the engine's programs through a cache of two
kinds of K/V (full-length rows, and rings a sliding layer writes at ``pos mod
ring``) with the toy window smaller than the prompt and the ring wrapping
more than twice, what a sliding and a full layer each see, the expert share
and the router, the ring form of the stacked decode kernel, and what
``Config.validate`` refuses."""

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

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.inference import InferenceEngine
from picotron_tpu.models import afmoe, experts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "trinity-large-ep32-l9"
CELL = NAME + ".serve-mixedctx-decode"
S, F = afmoe.WINDOW, afmoe.FULL

TOY = block_toys.TOYS["afmoe"]


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_afmoe",
        os.path.join(ROOT, "benchmarks", "reference", "afmoe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


make_config = partial(block_toys.make_config, "afmoe")


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, slots=2, max_seq_len=128,
                             **{"prefill_chunk": 8, **kw})
    params = jax.jit(lambda k: afmoe.init_params(k, cfg.model))(
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
PROMPT = [int(t) for t in RNG.integers(1, 256, 70)]
OTHER = [int(t) for t in RNG.integers(1, 256, 70)]


@pytest.fixture
def toy():
    return make_engine()


# ---- (a) the engine's programs against the reference ----------------------


@pytest.mark.parametrize("n_prompt,chunk,steps", [
    # ring 24 < prompt: 8 2/3 chunks (the last with two pad rows), the ring
    # written round almost three times, a decode step across pos 72 = 3 x 24
    (70, 8, 6),
    (6, 8, 4),     # one-shot, its bucket of 16 rows cut to a chunk's 8
])
def test_prefill_and_decode_match_the_reference(n_prompt, chunk, steps):
    _, engine, params = make_engine(prefill_chunk=chunk)
    seq, got, cache = program_logits(engine, params, PROMPT[:n_prompt],
                                     steps=steps)
    assert worst_rel_err(got, reference_rows(params, seq, n_prompt)) < 1e-4
    assert int(cache["lengths"][0]) == n_prompt + steps
    assert cache["kw"].shape[2] == 16 + chunk


def test_the_whole_forward_matches_the_reference_at_every_position(toy):
    """The one-shot program's layer functions over a whole sequence longer
    than the window (no cache): every position's logits."""
    cfg, engine, params = toy
    tokens = jnp.asarray([PROMPT[:50]], jnp.int32)

    def forward(params, tokens):
        h = engine._embed(params, tokens)
        live = jnp.ones(tokens.shape, bool)
        h, _, stats = engine._prefill_groups(
            params, h, engine._cos[:50], engine._sin[:50], live)
        return engine.model.head_logits(params, h, cfg), stats

    with engine.topo.mesh:
        logits, stats = jax.jit(jax.shard_map(
            forward, mesh=engine.topo.mesh,
            in_specs=(engine._pspecs, jax.sharding.PartitionSpec()),
            out_specs=jax.sharding.PartitionSpec(), check_vma=False))(
                params, tokens)
    want = ref.forward_logits(params, np.asarray(tokens), dict(TOY))[0]
    assert float(np.max(np.abs(np.asarray(logits[0]) - want))
                 / np.max(np.abs(want))) < 1e-4
    rows = dict(zip(afmoe.STAT_NAMES, np.asarray(stats).T))
    # sliding layers 0, 1 and 3: 1 + 2 + .. of the first 16 queries, then
    # 16 each; the full one counts nothing
    attended = sum(min(t + 1, 16) for t in range(50))
    assert list(rows["swa_rows_attended"]) == [attended, attended, 0, attended]
    assert list(rows["swa_rows_context"]) == [1275, 1275, 0, 1275]  # 50 x 51 / 2
    assert list(rows["moe_layer_steps"]) == [0, 1, 1, 1]
    assert not rows["swa_layer_steps"].any()


def test_a_slot_used_twice_forgets_its_first_occupant(toy):
    """The ring of a slot's second occupant holds the first one's rows
    where it has not written yet: rows of a position below zero, seen by
    nobody."""
    _, engine, params = toy
    _, _, cache = program_logits(engine, params, PROMPT, steps=3)
    cache = engine.release(cache, 0)
    _, again, _ = program_logits(engine, params, OTHER[:13], cache=cache)
    _, fresh, _ = program_logits(engine, params, OTHER[:13])
    np.testing.assert_allclose(np.stack(again), np.stack(fresh), atol=1e-6)


def test_a_decode_block_is_the_steps_one_by_one_across_a_wrap(toy):
    """Eight steps in one program from pos 70 (rows 22, 23, 0, 1, ..): the
    tokens the single steps give, a slot out of budget left where it was."""
    _, engine, params = toy
    cache = engine.init_cache()
    cache, last = admit(engine, params, cache, PROMPT, slot=0)
    cache, _ = admit(engine, params, cache, OTHER[:30], slot=1)
    first = int(np.argmax(last))
    step_cache, want, tok = cache, [], first
    solo = jax.tree.map(jnp.copy, cache)
    for _ in range(8):
        solo, logits = decode(engine, params, solo, tok, slot=0)
        tok = int(np.argmax(logits))
        want.append(tok)
    out = engine.decode_block(
        params, step_cache, np.asarray([first, 5], np.int32),
        jax.random.split(jax.random.PRNGKey(0), engine.decode_block_len),
        np.asarray([-1, -1], np.int32), np.asarray([8, 0], np.int32),
        np.zeros(2, np.float32), np.zeros(2, np.int32),
        np.ones(2, np.float32))
    tokens, counts, _ = out.host()
    assert tokens[0].tolist() == want and counts.tolist() == [8, 0]
    assert np.asarray(out.cache["lengths"]).tolist() == [78, 30]


# ---- (b) what each kind of layer sees ---------------------------------------


@pytest.mark.parametrize("window", [True, False])
def test_a_sliding_layer_forgets_what_a_full_layer_remembers(window):
    cfg = make_config()
    m = cfg.model
    group = jax.jit(lambda k: afmoe.init_params(k, m))(
        jax.random.PRNGKey(2))["moe_window_1" if window else "moe_full_2"]
    lp = {n: v[0] for n, v in group.items()}
    T = 40
    cos, sin = afmoe.serving_rope_tables(m, T, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, T, 64), jnp.float32)
    # everything at or before T - 1 - window changes
    other = h.at[:, :T - 16].set(
        jax.random.normal(jax.random.PRNGKey(4), (1, T - 16, 64)))

    @jax.jit
    def branch(x):
        out, _ = afmoe._layer(lp, x, cos, sin, cfg, dense=False,
                              window=window)
        return out - x

    def last_row(x):
        return np.asarray(branch(x)[0, -1])

    a, b = last_row(h), last_row(other)
    if window:
        np.testing.assert_allclose(a, b, atol=1e-6)
    else:
        assert np.max(np.abs(a - b)) > 1e-2


def test_ring_positions_by_hand():
    # ring of 6 after writing position 7: rows 0, 1 hold 6, 7; rows 2-5
    # hold 2-5
    assert afmoe.ring_positions(7, 6).tolist() == [6, 7, 2, 3, 4, 5]
    # not come round yet: rows past the last hold nothing (negative)
    assert afmoe.ring_positions(jnp.asarray([2]), 6).tolist() \
        == [[0, 1, 2, -3, -2, -1]]
    seen = afmoe.visible(jnp.asarray([[7]]),
                         afmoe.ring_positions(jnp.asarray([7]), 6), 4)
    assert seen[0, 0].tolist() == [True, True, False, False, True, True]
    assert afmoe.ring_rows(ModelConfig(sliding_window=4096), 32768, 512) \
        == 4608
    assert afmoe.ring_rows(ModelConfig(sliding_window=4096), 2048, 512) \
        == 2048
    assert afmoe.key_block(4608) == 1536 and afmoe.key_block(32768) == 2048


@pytest.mark.parametrize("T,W,block_t", [
    (48, 32, 8), (48, 32, 16), (48, 32, None), (80, 16, 16), (80, 16, None),
    (80, 16, 8)])
def test_the_ring_form_of_the_stacked_decode_kernel(T, W, block_t):
    """``flash_decode_stacked(window=)`` in interpret mode against the
    masked contraction of the layer's block: a ring not come round, one
    come round once and twice, a dead range that is block 0 whole (pos 88:
    row 40 is the query's, rows 0-7 are 33-40 behind it); and a ring of
    five blocks under a window of one (T 80, W 16): a window not full, the
    query's row a block's last (47, 79) and first (64), a window that wraps
    from the last block to block 0 (85), come round twice (197)."""
    from picotron_tpu.ops.pallas.decode_attention import flash_decode_stacked

    rng = np.random.default_rng(0)
    pos = jnp.asarray([0, 7, 47, 63, 88, 127] if T == 48 else
                      [0, 7, 40, 47, 64, 79, 85, 95, 160, 197], jnp.int32)
    L, B, nkv, D, nh = 3, len(pos), 2, 128, 8
    k, v = (jnp.asarray(rng.standard_normal((L, B, T, nkv, D)), jnp.bfloat16)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, 1, nh, D)), jnp.bfloat16)
    got = flash_decode_stacked(q, k, v, pos + 1, D ** -0.5, 1,
                               block_t=block_t, interpret=True, window=W)
    seen = afmoe.visible(pos[:, None], afmoe.ring_positions(pos, T), W)
    assert seen.sum(-1).ravel().tolist() == [min(int(p) + 1, W) for p in pos]
    want = afmoe.masked_attention(q, k[1], v[1], seen, D ** -0.5)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) < 2e-2
    # a free slot walks no block and comes out zeros, as in the plain form
    free = flash_decode_stacked(q[:2], k[:, :2], v[:, :2],
                                jnp.asarray([0, 8], jnp.int32), D ** -0.5, 1,
                                block_t=block_t, interpret=True, window=W)
    assert not free[0].any() and free[1].any()


def test_on_a_tpu_the_window_step_takes_the_kernel(monkeypatch):
    """``ring_attend`` routes bfloat16 rows of whole lanes to the kernel
    where ``on_tpu`` says so, and the float32 toy to the contraction."""
    from picotron_tpu.ops.pallas import decode_attention as da

    calls = []
    monkeypatch.setattr(afmoe, "on_tpu", lambda: True)
    monkeypatch.setattr(
        da, "flash_decode_stacked",
        lambda q, k, v, lengths, scale, layer, window: calls.append(
            (lengths.tolist(), window)) or q)
    kw = jnp.zeros((2, 3, 24, 2, 128), jnp.bfloat16)
    q = jnp.zeros((3, 1, 4, 128), jnp.bfloat16)
    pos = jnp.asarray([0, 30, 5], jnp.int32)
    afmoe.ring_attend(q, kw, kw, pos, 1, 16, 0.1)
    assert calls == [([1, 31, 6], 16)]
    afmoe.ring_attend(q.astype(jnp.float32), kw.astype(jnp.float32),
                      kw.astype(jnp.float32), pos, 1, 16, 0.1)
    afmoe.ring_attend(q, kw, kw, pos, 1, 16, 0.1, impl="dense")
    assert len(calls) == 1  # neither the float32 toy nor a dense engine


# ---- (c) the share and the router -------------------------------------------


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Ranks 0-3 of 4, two experts each, the shared expert counted once,
    against the uncut layer of eight, and against the reference's."""
    uncut = make_config(dict(num_experts=8, ep_size=1)).model
    group = jax.jit(lambda k: afmoe.init_params(k, uncut))(
        jax.random.PRNGKey(5))["moe_window_1"]
    lp = {n: v[0] for n, v in group.items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64), jnp.float32)
    live = jnp.ones((2, 12), bool)

    def mlp(lp, x, m, live):
        return jax.jit(lambda lp, x, live: afmoe.expert_mlp(lp, x, m, live))(
            lp, x, live)

    whole, (assigned, hit, *_) = mlp(lp, x, uncut, live)
    assert int(assigned) == 2 * 12 * 2 and int(hit) <= 8
    shared = experts.swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total, held = shared, 0
    for rank in range(4):
        m = make_config(dict(ep_rank=rank)).model
        part = {**lp, **{n: lp[n][2 * rank:2 * rank + 2]
                         for n in ("w1", "w3", "w2")}}
        y, (n, *_) = mlp(part, x, m, live)
        total, held = total + (y - shared), held + int(n)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert held == 2 * 12 * 2  # every token's experts are held by some rank
    want = ref.experts(lp, x.reshape(24, 64), dict(TOY, num_experts=8,
                                                   ep_size=1))
    np.testing.assert_allclose(whole.reshape(24, 64), want, atol=2e-5)
    # rows that are not live are routed nowhere: the shared expert alone
    y, (n, *_) = mlp(lp, x, uncut, jnp.zeros((2, 12), bool))
    np.testing.assert_allclose(y, shared, atol=1e-6)
    assert int(n) == 0


def test_the_bias_moves_the_choice_and_not_the_weights():
    scores = jnp.asarray([[0.875, 0.75, 0.125, 0.9375, 0.5625, 0.5,
                           0.6875, 0.625]])
    chosen, w = experts.route(scores, jnp.zeros(8), k=2, scale=2.448,
                              eps=afmoe.ROUTE_EPS)
    assert chosen.tolist() == [[3, 0]]
    np.testing.assert_allclose(
        w[0], np.array([0.9375, 0.875]) / 1.8125 * 2.448, rtol=1e-6)
    bias = jnp.zeros(8).at[5].set(0.4)  # 0.9 biased: between the two
    chosen, w = experts.route(scores, bias, k=2, scale=2.448,
                              eps=afmoe.ROUTE_EPS)
    assert chosen.tolist() == [[3, 5]]
    np.testing.assert_allclose(
        w[0], np.array([0.9375, 0.5]) / 1.4375 * 2.448, rtol=1e-6)
    # the reference's router agrees (it takes x and the router's matrix)
    logit = jnp.log(scores / (1 - scores))
    r_chosen, r_w = ref.route(logit, jnp.eye(8), bias, k=2, scale=2.448)
    assert r_chosen.tolist() == chosen.tolist()
    np.testing.assert_allclose(r_w, w, rtol=1e-5)


# ---- (d) groups, cache, counts ----------------------------------------------


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


def test_layer_groups_are_the_runs_of_equal_layers():
    m = make_config().model
    groups = afmoe.layer_groups(m)
    assert [(n, c) for n, _, c in groups] == [
        ("dense_window_0", 1), ("moe_window_1", 1), ("moe_full_2", 1),
        ("moe_window_3", 1)]
    # where each run's rows of its own kind's leaves begin
    assert [fn.keywords["kind_first"] for _, fn, _ in groups] \
        == [0, 1, 0, 2]
    real = afmoe.layer_groups(published_model())
    assert [(n, c) for n, _, c in real] == [
        ("dense_window_0", 1), ("moe_window_1", 3), ("moe_full_2", 1),
        ("moe_window_3", 3), ("moe_full_4", 1)]
    assert [fn.keywords["kind_first"] for _, fn, _ in real] \
        == [0, 1, 0, 4, 1]


def test_the_cache_holds_two_kinds_of_kv_at_the_cells_sizes():
    """16 slots x 32,768: 4,608 rows a slot in the seven sliding layers,
    32,768 in the two full ones: 6.41 GB, where nine full-length layers
    would be 19.3."""
    m = published_model()
    shapes = jax.eval_shape(lambda: afmoe.init_cache(
        m, 16, 32768, prefill_chunk=512))
    assert shapes["k"].shape == shapes["v"].shape == (2, 16, 32768, 8, 128)
    assert shapes["kw"].shape == shapes["vw"].shape == (7, 16, 4608, 8, 128)
    assert shapes["k"].dtype == shapes["kw"].dtype == jnp.bfloat16
    nbytes = sum(v.size * v.dtype.itemsize for v in shapes.values())
    assert round(nbytes / 1e9, 2) == 6.41
    assert round(9 * 16 * 32768 * 4096 / 1e9, 1) == 19.3
    assert set(afmoe.cache_pspecs(m)) == set(shapes)


def test_parameters_are_the_opcounts():
    sys.path.insert(0, ROOT)
    from benchmarks import opcount_afmoe as oa

    pub = published_config()
    assert afmoe.num_params(published_model()) == oa.num_params(pub) \
        == 2_878_065_920
    assert round(2 * oa.num_params(pub) / 1e9, 2) == 5.76
    assert oa.layer_params(pub, True) == 176_173_312
    assert oa.layer_params(pub, False) == 318_517_760
    assert oa.cache_bytes(pub, 16, 32768, 512) == (4_294_967_296,
                                                   2_113_929_216)
    toy_m = make_config().model
    p = jax.eval_shape(lambda: afmoe.init_params(jax.random.PRNGKey(0),
                                                 toy_m))
    assert afmoe.num_params(toy_m) == sum(
        v.size for v in jax.tree.leaves(p)) == oa.num_params(
            dict(TOY, torch_dtype="float32"))
    # a step of slots at 2,000 and 20,000: the weights but the embedding,
    # 22,000 rows in each full layer, 2,000 + 4,096 in each sliding one
    got = oa.decode_step_bytes(pub, [2000, 20000])
    assert got == 2 * (2_878_065_920 - 25024 * 3072) \
        + (2 * 22000 + 7 * 6096) * 4096
    assert oa.window_attend_bytes(pub, [2000, 20000]) == 6096 * 4096


def test_seeded_draws_are_as_the_configuration_file_says(toy):
    cfg, _, params = toy
    g = params["moe_window_1"]
    assert g["router_bias"].dtype == jnp.float32
    bias = np.abs(np.asarray(g["router_bias"]))
    assert 0 < bias.max() <= afmoe.ROUTER_BIAS and bias.min() > 0
    bound = (1 / 32) ** 0.5 * afmoe.INIT_GAIN["w2"]
    assert 0.9 * bound < np.abs(np.asarray(g["w2"])).max() <= bound
    # a unit-rms row of the embedding, once multiplied by sqrt(hidden)
    rows = np.linalg.norm(np.asarray(params["embed"]), axis=1)
    assert 0.7 < rows.mean() < 1.3
    assert params["lm_head"].shape == (64, 256)


# ---- (e) refused by name ----------------------------------------------------


def test_training_is_refused_by_name():
    cfg = make_config()
    with pytest.raises(ValueError, match="afmoe.*served, not trained"):
        cfg.validate(for_training=True)


@pytest.mark.parametrize("model,match", [
    ({"layer_types": [S] * 4}, "at least one 'sliding_attention' and one"),
    ({"layer_types": [S, F]}, "for each of the 4 layers"),
    ({"layer_types": [S, "attention", F, S]}, "layer_types"),
    ({"sliding_window": 0}, "sliding_window >= 1"),
    ({"num_dense_layers": 4}, "num_dense_layers 4 outside"),
    ({"n_group": 2}, "n_group = 1 only"),
    ({"score_func": "softmax"}, "score_func = 'sigmoid' only"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings = False only"),
    ({"ep_rank": 4}, "ep_rank 4 outside"),
    ({"num_experts_per_tok": 9}, "passes the router's width 8"),
    # published layers 4-6 of a model that is full every fourth layer:
    # the fifth is not
    ({"global_attn_every_n_layers": 4, "first_layer": 4,
      "total_layers": 12}, "layer_types\\[2\\].*published layer 5"),
])
def test_validate_refuses_what_the_block_lacks(model, match):
    with pytest.raises(ValueError, match="afmoe.*" + match):
        make_config(model)


def test_validate_places_the_held_layers_in_the_published_model():
    """The dense layer is published layer 0, the expert layers 6-8 of 12:
    full where (index + 1) % 4 == 0."""
    make_config({"global_attn_every_n_layers": 4, "first_layer": 6,
                 "total_layers": 12})
    m = published_model()
    assert (m.first_layer, m.total_layers, m.head_dim) == (8, 60, 128)
    assert m.hidden_size // m.num_attention_heads == 64


def test_head_dim_is_given_or_derived():
    m = ModelConfig(hidden_size=512, num_attention_heads=4)
    assert m.head_dim == 128
    m.hidden_size = 256  # derived: it follows
    assert m.head_dim == 64
    given = ModelConfig(hidden_size=3072, num_attention_heads=48,
                        head_dim=128)
    assert given.head_dim == 128
    d = make_config().to_dict()
    assert d["model"]["head_dim"] == 32
    assert Config().to_dict()["model"]["head_dim"] == 0
    assert Config.from_dict(Config().to_dict()).model.head_dim == 64


def test_engine_keywords_are_refused_too():
    cfg = make_config()
    with pytest.raises(ValueError, match="afmoe.*speculation"):
        InferenceEngine(cfg, slots=2, max_seq_len=128, spec_len=2)
    with pytest.raises(ValueError, match="afmoe.*kv_layout 'paged'"):
        InferenceEngine(cfg, slots=2, max_seq_len=128, kv_layout="paged")


# ---- (f) the controls, the counters -----------------------------------------


def test_bfloat16_fails_the_float32_check(toy):
    """The program in the nearest precision below fails the 1e-3 the
    float32 engine is held to (the chunked prefill's logits: enough to
    tell, and the decode program need not compile again)."""
    _, engine, params = toy
    want = reference_rows(params, PROMPT, len(PROMPT))
    _, last = admit(engine, params, engine.init_cache(), PROMPT)
    assert worst_rel_err([last], want) < 1e-3
    _, low, _ = make_engine({"dtype": "bfloat16"})
    low_params = low.shard_params(jax.tree.map(
        lambda v: v if v.dtype == jnp.float32 and v.ndim == 2
        and v.shape[-1] == 8 else v.astype(jnp.bfloat16), params))
    _, last = admit(low, low_params, low.init_cache(), PROMPT)
    assert worst_rel_err([last], want) > 1e-3


@pytest.mark.parametrize("fault", ["window_ignored", "ring_a_chunk_short"])
def test_a_fault_in_the_ring_fails_the_check(fault, monkeypatch, toy):
    """The two controls the cell's ``correct`` must see, at toy size, in
    the chunked prefill's logits: the window not applied in the sliding
    layers; a ring of ``sliding_window`` rows only, whose chunks overwrite
    keys their first queries still see."""
    _, _, params = toy
    want = reference_rows(params, PROMPT, len(PROMPT))
    if fault == "window_ignored":
        monkeypatch.setattr(
            afmoe, "visible", lambda pq, pk, window: (
                (pk[:, None, :] >= 0) & (pk[:, None, :] <= pq[:, :, None])))
    else:
        monkeypatch.setattr(afmoe, "ring_rows",
                            lambda m, max_seq_len, chunk: m.sliding_window)
    _, engine, _ = make_engine(fresh=True)  # traced under the patch
    _, last = admit(engine, params, engine.init_cache(), PROMPT)
    assert worst_rel_err([last], want) > 1e-2


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
    for name in afmoe.STAT_NAMES:
        line, = [l for l in text.splitlines()
                 if l.startswith(f"picotron_{name}_total ")]
        got[name] = float(line.split()[1])

    def rows(n, cap):  # a prompt's queries, then its four decode steps
        return sum(min(t + 1, cap) for t in range(n + 4))

    # three sliding layers
    assert got["swa_rows_context"] == 3 * sum(
        rows(n, 10 ** 6) for n in (44, 6, 20))
    assert got["swa_rows_attended"] == 3 * sum(
        rows(n, 16) for n in (44, 6, 20))
    assert got["swa_layer_steps"] % 3 == 0 and got["swa_layer_steps"] >= 3 * 8
    assert got["moe_layer_steps"] > 0 and got["moe_assignments"] > 0
    # three requests on two slots: the third took a slot a first one left,
    # and its stream is what it is alone in a fresh engine
    alone = ContinuousBatcher(shared, params, seed=0).run(
        [Request(uid="x", prompt=OTHER[:20], max_new_tokens=5)])
    assert out["r2"].tokens == alone["x"].tokens


# ---- (g) the cell ------------------------------------------------------------


def test_rehearsal_of_the_cell_computes_its_readers():
    """The cell's control flow at toy size on the CPU. Steadied against a
    loaded machine (six test workers): the mix's rehearsal leads in for 3 s
    and its requests cycle in well under the window, and the window here is
    4 s, so that decode rounds fall inside it whatever a first prefill
    waited for."""
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
    assert {"serve_out_tokens_per_s", "setup_s", "swa.attended_pct",
            "moe.held_assignments_per_step.afmoe"} <= set(out["computed"])


def test_a_program_without_the_block_fails_the_cell_at_once():
    """What the parent does with the new cell: ``sliding_window``, the first
    of ``model_keys``, is a name its ``ModelConfig`` lacks, and the run ends
    with exit code 2 before any device work (here: a configuration that
    lists one more)."""
    sys.path.insert(0, ROOT)
    from benchmarks import common

    config = published_config()
    assert config["model_keys"][0] == "sliding_window"
    m = common.model_section(config)
    assert m["model_type"] == "afmoe" and m["sliding_window"] == 4096
    assert m["head_dim"] == 128 and m["num_experts"] == 8
    assert common.load_reference(config).__file__.endswith("afmoe.py")
    config["model_keys"] = ["window_mystery"] + config["model_keys"]
    config["window_mystery"] = 1
    with pytest.raises(SystemExit) as e:
        common.model_section(config)
    assert e.value.code == 2


def test_the_configuration_keeps_every_published_number():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Trinity-Large-Preview")
    config = published_config()
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_dense_layers", "num_experts",
                                 "vocab_size", "ep_size"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["reduced_from"]["num_experts"] == row["config"][
        "num_experts"] == config["num_experts"] * config["ep_size"]
    pub = row["config"]["layer_types"]
    first = config["first_layer"]
    assert config["layer_types"] == [pub[0]] + pub[first:first + 8]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["reduced"] == config["reduced"]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] \
        == "mixedctx-decode-closed-32k"
