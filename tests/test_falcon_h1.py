"""The Falcon-H1 block (models/falcon_h1.py) on the serving path, at toy size
on the CPU with seeded weights, against the plain reference
(benchmarks/reference/falcon_h1.py): a parallel hybrid whose every layer
keeps a K/V row AND a state row (five query heads a K/V head, two B/C groups,
``d_ssm`` that is not twice the hidden size, every published multiplier), the
engine's programs through its four cache leaves, what a state with no token
axis asks of them, the two kernels of its decode step at the cell's shapes in
interpret mode, the Mamba-2 mixer lifted into ``models/mamba2.py`` against the
two mixers it replaced, and what ``Config.validate`` refuses."""

from functools import partial
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import block_toys
import parent_mamba_mixers as parent
from engine_memo import (admit, decode, memoized, program_logits,
                         worst_rel_err)

from picotron_tpu.config import Config
from picotron_tpu.inference import InferenceEngine
from picotron_tpu.inference.kv_cache import decode_attention
from picotron_tpu.models import falcon_h1 as fh
from picotron_tpu.models import granite_hybrid, model_module, nemotron_h
from picotron_tpu.ops.pallas.decode_attention import flash_decode_stacked
from picotron_tpu.ops.pallas.ssm_step import head_block, ssm_step_stacked
from picotron_tpu.ops.ssm import ssm_scan, ssm_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = block_toys.TOYS["falcon_h1"]
LAYERS = TOY["num_hidden_layers"]
F32 = jnp.float32


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_falcon_h1",
        os.path.join(ROOT, "benchmarks", "reference", "falcon_h1.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()
make_config = partial(block_toys.make_config, "falcon_h1")


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, slots=2, max_seq_len=128,
                             **{"prefill_chunk": 16, **kw})
    params = jax.jit(lambda k: fh.init_params(k, cfg.model))(
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
    (44, 16),   # three chunks: a boundary inside the prompt, a padded last
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
    stats = dict(zip(fh.STAT_NAMES, engine.take_stats()))
    assert stats["ssm_tokens_scanned"] == LAYERS * n_prompt
    assert stats["ssm_state_updates"] == stats["ssm_layer_steps"] \
        == stats["attn_layer_steps"] == LAYERS * 4
    # a step's attend covers the context, the fresh row's own key in
    assert stats["attn_keys_read"] == LAYERS * sum(
        n_prompt + i + 1 for i in range(4))


def test_a_bfloat16_model_is_inside_the_serving_limit():
    """bfloat16 weights, stream and K/V, the float32 state beside them,
    against the float32 reference fed the same bfloat16 tree: the runner's
    limit for such a model (3 % of max |logit|)."""
    _, engine, params = make_engine({"dtype": "bfloat16"})
    seq, got, cache = program_logits(engine, params, PROMPT)
    assert cache["ssm"].dtype == F32 and cache["k"].dtype == jnp.bfloat16
    assert worst_rel_err(got, reference_rows(params, seq, len(PROMPT))) < 3e-2


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
    n = len(PROMPT) + 4
    for name in ("k", "v"):
        np.testing.assert_allclose(ca[name][:, 0, :n], cb[name][:, 0, :n],
                                   atol=1e-5, rtol=1e-5)


# ---- (b) a state with no token axis beside K/V -----------------------------


def test_pad_rows_leave_state_and_conv_tail_as_at_length():
    _, padded, params = make_engine(prefill_chunk=64)  # 21 -> bucket 32
    _, exact, _ = make_engine(prefill_chunk=64, min_prefill_bucket=21)
    prompt = PROMPT[:21]
    kv_p, last_p = padded.prefill(params, prompt)
    kv_e, last_e = exact.prefill(params, prompt)
    # every leaf over ALL layers: a layer keeps both kinds of row
    assert kv_p["ssm"].shape == (LAYERS, 1, 8, 12, 16)
    assert kv_p["ssm"].dtype == F32
    assert kv_p["conv"].shape == (LAYERS, 1, 3, 96 + 2 * 2 * 16)
    assert kv_p["k"].shape == (LAYERS, 1, 32, 2, 16)
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
    """The first chunk starts from zeros whatever the slot held, and the
    first occupant's stale K/V rows hide behind the length."""
    _, engine, params = make_engine(prefill_chunk=chunk)
    _, _, cache = program_logits(engine, params, PROMPT)
    assert np.abs(np.asarray(cache["ssm"][:, 0])).max() > 0
    cache = engine.release(cache, 0)
    assert np.abs(np.asarray(cache["k"][:, 0])).max() > 0  # still there
    seq, got, _ = program_logits(engine, params, OTHER[:30], cache=cache)
    assert worst_rel_err(got, reference_rows(params, seq, 30)) < 1e-3


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
    stats = dict(zip(fh.STAT_NAMES, engine.take_stats()))
    # 8 steps x 3 layers ran; slot 0 advanced in 3 of the steps, at
    # contexts of 45, 46 and 47 keys
    assert stats["ssm_layer_steps"] == stats["attn_layer_steps"] \
        == 8 * LAYERS
    assert stats["ssm_state_updates"] == 3 * LAYERS
    assert stats["attn_keys_read"] == LAYERS * (45 + 46 + 47)
    # slot 1 decodes on from where it stood, as the reference has it
    seq = OTHER[:30] + [int(toks[1])]
    _, logits = decode(engine, params, r.cache, seq[-1], 1)
    assert worst_rel_err([logits], reference_rows(params, seq, 31)) < 1e-3


@pytest.mark.parametrize("rounded", [False, True])
def test_the_state_of_a_bfloat16_model_is_float32_all_the_way(monkeypatch,
                                                              rounded):
    """The serving check's logits may not tell a state kept in bfloat16
    from the float32 the configuration states (``control_falcon.py``'s
    ``state_bf16``). This can: after a chunked admission and decode steps of
    a bfloat16 model next to none of the state's entries are ones bfloat16
    holds exactly; rounded anywhere on its way, all are."""
    if rounded:
        mixer = fh.mamba_mixer

        def rounding(*args, **kw):
            out, conv_out, ssm_out = mixer(*args, **kw)
            return out, conv_out, jax.lax.reduce_precision(
                ssm_out, exponent_bits=8, mantissa_bits=7)

        monkeypatch.setattr(fh, "mamba_mixer", rounding)
    _, engine, params = make_engine({"dtype": "bfloat16"}, fresh=True)
    _, _, cache = program_logits(engine, params, PROMPT)  # 3 chunks, 4 steps
    state = cache["ssm"][:, 0]
    assert state.dtype == F32 and cache["conv"].dtype == jnp.bfloat16
    there = state != 0
    exact = state.astype(jnp.bfloat16).astype(F32) == state
    share = float(jnp.sum(exact & there) / jnp.sum(there))
    assert share == 1.0 if rounded else share < 0.01, share


def test_the_window_is_held_to_whole_chunks():
    assert fh.CARRIES_STATE
    with pytest.raises(ValueError, match="multiple of prefill_chunk"):
        InferenceEngine(make_config(), slots=2, max_seq_len=120,
                        prefill_chunk=16)


# ---- (c) every multiplier is heard, and none is folded into a weight ------

# the published multipliers, each at another value: the seven scalars, the
# MLP's two one at a time, the mixer's five all at once (each at its own, so
# that one standing over another's columns shows) and with B's and C's
# exchanged (``control_falcon.py``'s fault, at toy size)
_Z, _X, _B, _C, _DT = TOY["ssm_multipliers"]
OTHER_VALUE = {
    "embedding_multiplier": {"embedding_multiplier": 3.0},
    "key_multiplier": {"key_multiplier": 0.03},
    "attention_in_multiplier": {"attention_in_multiplier": 0.5},
    "attention_out_multiplier": {"attention_out_multiplier": 0.1},
    "ssm_in_multiplier": {"ssm_in_multiplier": 0.5},
    "ssm_out_multiplier": {"ssm_out_multiplier": 0.2},
    "lm_head_multiplier": {"lm_head_multiplier": 0.01},
    "mlp_gate": {"mlp_multipliers": [0.05, TOY["mlp_multipliers"][1]]},
    "mlp_down": {"mlp_multipliers": [TOY["mlp_multipliers"][0], 0.03]},
    "ssm_all_five": {"ssm_multipliers": [0.9, 0.7, 0.5, 0.3, 0.8]},
    "ssm_b_and_c_exchanged": {"ssm_multipliers": [_Z, _X, _C, _B, _DT]},
}


@pytest.mark.parametrize("name", sorted(OTHER_VALUE))
def test_every_multiplier_is_heard(name):
    """The SAME seeded tree under a configuration with one multiplier at
    another value: the program's logits move, and move to where the
    reference's do,
    so each is applied in the forward pass where the equations put it (the
    draw's ``_undone`` is the base configuration's and cannot hide it)."""
    _, base, params = make_engine(prefill_chunk=64)
    _, got_base, _ = program_logits(base, params, PROMPT[:24], steps=0)
    model = OTHER_VALUE[name]
    _, engine, _ = make_engine(model, prefill_chunk=64)
    seq, got, _ = program_logits(engine, params, PROMPT[:24], steps=0)
    assert worst_rel_err(got, got_base) > 1e-2
    want = reference_rows(params, seq, 24, dict(TOY, **model))
    assert worst_rel_err(got, want) < 1e-3


def test_the_mup_vector_stands_over_in_projs_five_ranges():
    m = make_config().model
    v = np.asarray(fh.mup_vector(m, F32))
    z, x, b, c, dt = TOY["ssm_multipliers"]
    assert v.shape == (96 + 96 + 32 + 32 + 8,)
    want = [z] * 96 + [x] * 96 + [b] * 32 + [c] * 32 + [dt] * 8
    np.testing.assert_allclose(v, want, rtol=1e-6)


# ---- (d) the decode step's two kernels at the cell's shapes ----------------


def bit_equal(a, b):
    return np.array_equal(np.asarray(a).view(np.uint32),
                          np.asarray(b).view(np.uint32))


def test_ssm_step_kernel_at_32_heads_of_128_by_256_in_two_groups():
    """``ssm_step_stacked`` in interpret mode at the cell's state (a head's
    128 x 256 is four times Granite's and Nemotron's; B and C a group of
    sixteen heads; the rule's block of 8 heads, 1 MiB) against ``ssm_step``:
    the row advanced, every other row and a ``dt = 0`` slot bit for bit."""
    rows, slots, heads, hd, N, G = 2, 2, 32, 128, 256, 2
    assert head_block(heads, heads // G, 4 * hd * N) == 8
    ks = jax.random.split(jax.random.key(3), 6)
    xs = jax.random.normal(ks[0], (slots, 1, heads, hd), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (slots, 1, heads), F32))
    dt = dt.at[1].set(0.0)
    A = -jnp.exp(jax.random.normal(ks[2], (heads,), F32))
    Bm = jax.random.normal(ks[3], (slots, 1, G, N), jnp.bfloat16)
    Cm = jax.random.normal(ks[4], (slots, 1, G, N), jnp.bfloat16)
    leaf = jax.random.normal(ks[5], (rows, slots, heads, hd, N), F32)
    y, out = ssm_step_stacked(xs, dt, A, Bm, Cm, leaf, jnp.int32(1),
                              interpret=True)
    y_ref, state_ref = ssm_step(xs, dt, A, Bm, Cm, leaf[1])
    np.testing.assert_allclose(out[1], state_ref, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(y, y_ref, rtol=0, atol=2e-5 * float(
        jnp.max(jnp.abs(y_ref))))
    assert bit_equal(out[0], leaf[0]) and bit_equal(out[1, 1], leaf[1, 1])
    # a head reads its own group: with the groups exchanged it differs
    y_x, _ = ssm_step(xs, dt, A, Bm[:, :, ::-1], Cm[:, :, ::-1], leaf[1])
    assert float(jnp.max(jnp.abs(y_x[0] - y_ref[0]))) > 1.0


def test_ssm_scan_at_chunk_128_and_state_256_is_the_recurrence():
    """The chunked scan at the cell's chunk and state width (two groups,
    one whole chunk and a part of one) against the row-by-row step."""
    B, S, heads, hd, N, G = 1, 150, 4, 8, 256, 2
    ks = jax.random.split(jax.random.key(4), 6)
    xs = jax.random.normal(ks[0], (B, S, heads, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, heads)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=2.7))
    Bm = jax.random.normal(ks[3], (B, S, G, N)) / 16
    Cm = jax.random.normal(ks[4], (B, S, G, N)) / 16
    S0 = jax.random.normal(ks[5], (B, heads, hd, N))
    y, state = jax.jit(partial(ssm_scan, chunk=128))(xs, dt, A, Bm, Cm, S0)

    def row(s, t):
        y_t, s = ssm_step(*(a[:, None] for a in t[:2]), A,
                          *(a[:, None] for a in t[2:]), s)
        return s, y_t[:, 0]

    s, want = jax.lax.scan(row, S0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (xs, dt, Bm, Cm)))
    np.testing.assert_allclose(y, jnp.moveaxis(want, 0, 1), atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_allclose(state, s, atol=2e-4, rtol=2e-4)


def test_stacked_flash_decode_at_five_query_heads_a_kv_head():
    """``flash_decode_stacked`` in interpret mode at 20 query heads on 4
    K/V heads of 128 (a score tile of five rows, not a whole sublane
    group) against the dense rule, a traced layer, lengths from a free slot
    to the whole window."""
    L, T, kvh, g, D = 2, 512, 4, 5, 128
    lengths = jnp.asarray([0, 1, 77, 300, 512], jnp.int32)
    rng = np.random.default_rng(20)
    q = jnp.asarray(rng.normal(size=(5, 1, kvh * g, D)), jnp.bfloat16)
    k, v = (jnp.asarray(rng.normal(size=(L, 5, T, kvh, D)), jnp.bfloat16)
            for _ in range(2))
    scale = D ** -0.5
    kernel = jax.jit(lambda q, k, v, n, layer: flash_decode_stacked(
        q, k, v, n, scale, layer, block_t=128, interpret=True))
    dense = jax.jit(lambda q, k, v, n, layer: decode_attention(
        q, k[layer], v[layer], n, scale))
    for layer in (0, 1):
        got = np.asarray(kernel(q, k, v, lengths, jnp.int32(layer)), np.float32)
        want = np.asarray(dense(q, k, v, lengths, jnp.int32(layer)),
                          np.float32)
        assert got.shape == want.shape == (5, 1, 20, 128)
        np.testing.assert_allclose(got[1:], want[1:], rtol=2e-2, atol=2e-2)
        assert np.all(got[0] == 0.0)  # the free slot


# ---- (e) the lifted mixer is the two it replaced, bit for bit --------------


def _mixer_operands(block, module, group):
    cfg = block_toys.make_config(block)
    m = cfg.model
    params = jax.jit(lambda k: module.init_params(k, m))(jax.random.PRNGKey(3))
    lp = jax.tree.map(lambda v: v[0], {
        n: v for n, v in params[group].items()
        if n in ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                 "gate_norm", "out_proj")})
    ks = jax.random.split(jax.random.key(9), 3)
    B, S, W = 2, 21, module.conv_width(m)
    x = jax.random.normal(ks[0], (B, S, m.hidden_size), F32)
    conv = jax.random.normal(ks[1], (B, 3, W), F32)
    ssm = jax.random.normal(ks[2], (B, 8, 16, 16), F32)
    live = jnp.arange(S)[None, :] < jnp.asarray([S, 13])[:, None]
    return lp, m, x, conv, ssm, live


@pytest.mark.parametrize("block,module,group,old", [
    ("granitemoehybrid", granite_hybrid, "mamba_0", parent.granite_mixer),
    ("nemotron_h", nemotron_h, "me_0", parent.nemotron_mixer)])
@pytest.mark.parametrize("one_step", [False, True])
def test_the_lifted_mixer_is_the_parents_bit_for_bit(block, module, group,
                                                     old, one_step):
    """``models/mamba2.py::mixer`` through each block's ``mamba_mixer``
    against that block's mixer as it stood (``parent_mamba_mixers.py``), on
    the block's toy: a block of rows with a pad tail through the scan, and a
    decode step on a row of the stacked leaf; output, conv tail and state."""
    lp, m, x, conv, ssm, live = _mixer_operands(block, module, group)
    if one_step:
        x, live = x[:, :1], live[:, :1]
        ssm, step = jnp.stack([ssm, 2 * ssm, 3 * ssm]), (jnp.int32(1),)
    else:
        step = ()
    got = jax.jit(lambda *a: module.mamba_mixer(*a, m, step))(
        lp, x, conv, ssm, live)
    want = jax.jit(lambda *a: old(*a, m, step))(lp, x, conv, ssm, live)
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert bit_equal(a, b)


# ---- (f) the tree, the cache, the counters ---------------------------------


def test_the_tree_and_the_cache_of_the_toy():
    cfg, engine, params = make_engine()
    assert model_module(cfg.model) is fh
    assert [(n, c) for n, _, c in fh.layer_groups(cfg.model)] == \
        [("layers", LAYERS)]
    g = params["layers"]
    assert g["in_proj"].shape == (LAYERS, 80, 96 + 96 + 2 * 2 * 16 + 8)
    assert g["out_proj"].shape == (LAYERS, 96, 80)  # d_ssm is not 2 x 80
    assert g["wq"].shape == (LAYERS, 80, 160) and g["wk"].shape \
        == (LAYERS, 80, 32)
    assert g["w_gate"].shape == g["w_up"].shape == (LAYERS, 80, 96)
    assert "lm_head" in params  # untied, as published
    cache = engine.init_cache()
    assert cache["k"].shape == cache["v"].shape == (LAYERS, 2, 128, 2, 16)
    assert cache["ssm"].shape == (LAYERS, 2, 8, 12, 16)
    assert cache["ssm"].dtype == F32
    assert cache["conv"].shape == (LAYERS, 2, 3, 160)
    assert fh.num_params(cfg.model) == sum(
        v.size for v in jax.tree.leaves(params))
    A = np.exp(np.asarray(g["A_log"]))
    assert 1.0 <= A.min() and A.max() <= 16.0 and (np.asarray(g["D"]) == 1
                                                   ).all()
    # the draw makes up for a multiplier, the forward applies it: W_k is
    # drawn 1 / key_multiplier wider (x INIT_GAIN), W_v as it is
    bound = np.sqrt(1 / 80)
    assert 0.9 * bound < np.abs(np.asarray(g["wv"])).max() <= bound
    wide = fh.INIT_GAIN["wk"] * bound / TOY["key_multiplier"]
    assert 0.9 * wide < np.abs(np.asarray(g["wk"])).max() \
        <= (1 + fh.SELF_KEY) * wide
    # and a K/V head's keys lean on its group's first query head
    first = np.asarray(g["wq"]).reshape(LAYERS, 80, 2, 5, 16)[:, :, :, 0]
    lean = np.corrcoef(first.ravel(), np.asarray(g["wk"]).ravel())[0, 1]
    assert 0.4 < lean < 0.65  # SELF_KEY / sqrt(1 + SELF_KEY^2) = 0.51


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
    for name in fh.STAT_NAMES:
        line, = [l for l in text.splitlines()
                 if l.startswith(f"picotron_{name}_total ")]
        got[name] = float(line.split()[1])
    assert got["ssm_tokens_scanned"] == LAYERS * (44 + 9 + 20)
    assert got["ssm_state_updates"] == LAYERS * 3 * 4
    assert got["attn_layer_steps"] == got["ssm_layer_steps"] > 0
    # each request's four decode steps cover its prompt + 1 .. + 4 keys
    assert got["attn_keys_read"] == LAYERS * sum(
        4 * n + 10 for n in (44, 9, 20))
    _, fresh, _ = make_engine()
    alone = ContinuousBatcher(fresh, params, seed=0).run(
        [Request(uid="x", prompt=OTHER[:20], max_new_tokens=5)])
    assert out["r2"].tokens == alone["x"].tokens


# ---- (g) what is refused, by name ------------------------------------------


@pytest.mark.parametrize("model,match", [
    ({"mamba_d_ssm": 128}, "mamba_d_ssm"),
    ({"mamba_n_groups": 3}, "mamba_n_groups"),
    ({"ssm_multipliers": [1.0, 1.0, 1.0]}, "ssm_multipliers"),
    ({"mlp_multipliers": None}, "mlp_multipliers"),
    ({"mamba_norm_before_gate": True}, "mamba_norm_before_gate"),
    ({"mamba_rms_norm": False}, "mamba_rms_norm"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"mamba_d_state": 0}, "mamba_d_state"),
    ({"model_type": "falcon"}, "unknown model_type"),
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


# ---- (h) the serving control, the configuration file -----------------------


@pytest.mark.parametrize("what", ["bfloat16", "one key short"])
def test_a_lower_precision_and_a_missing_key_fail_the_float32_check(
        what, monkeypatch):
    """The float32 check's controls at toy size: the model in bfloat16, and
    a decode step's attend that leaves out the fresh row's own key (one key
    of ~45: what the chip's check at 1,536 keys may not hear,
    ``control_falcon.py``)."""
    _, engine, params = make_engine()
    seq, got, _ = program_logits(engine, params, PROMPT)
    want = reference_rows(params, seq, len(PROMPT))
    assert worst_rel_err(got, want) < 1e-3
    if what == "bfloat16":
        _, low, _ = make_engine({"dtype": "bfloat16"})
        low_params = low.shard_params(jax.tree.map(
            lambda v: v.astype(jnp.bfloat16) if v.ndim > 2 or v.shape[-1] > 8
            else v, params))
    else:
        from picotron_tpu.inference import kv_cache

        attend = kv_cache.attend
        monkeypatch.setattr(
            kv_cache, "attend", lambda q, cache, lengths, *a, **kw: attend(
                q, cache, lengths - (q.shape[1] == 1), *a, **kw))
        _, low, low_params = make_engine(fresh=True)
    cache, last = admit(low, low_params, low.init_cache(), PROMPT)
    got_low = [last]
    for tok in seq[len(PROMPT):]:
        cache, logits = decode(low, low_params, cache, tok)
        got_low.append(logits)
    assert worst_rel_err(got_low, want) > 1e-3


@pytest.mark.parametrize("fault,failed", [
    (None, []),
    ("state_bf16", ["bf16_exact"]),      # logits and values pass
    ("state_dropped", ["logits", "state"]),
    ("first_chunk_rows_stale", ["logits", "state"]),
])
def test_the_chips_check_holds_the_state_as_well_as_the_logits(fault, failed):
    """``benchmarks/runners/serve_state.py``'s check, the cell's ``correct``,
    on the bfloat16 toy at three chunks and four decode steps: the sound
    program passes its four limits; a state rounded to bfloat16 wherever
    it is stored passes the logits' two and the values' and fails the
    resolution's, and the check with it; a state dropped at a chunk boundary
    and a first chunk's K/V rows left unwritten fail the logits' and the
    values'."""
    import sys
    sys.path.insert(0, ROOT)
    from benchmarks.runners import serve, serve_state
    from benchmarks.tests import control_falcon

    ctx = {"config": dict(TOY, torch_dtype="bfloat16"), "reference": ref}
    with control_falcon.fault(fault):
        _, engine, params = make_engine({"dtype": "bfloat16"},
                                        fresh=fault is not None)
        ok, rows = serve_state.logits_check(ctx, engine, params, PROMPT)
    assert len(rows) == serve.CHECK_DECODE_STEPS + 4
    limits = {"logits": all(r[-1] for r in rows[:-2]),
              "state": rows[-2][-1], "bf16_exact": rows[-1][-1]}
    assert [k for k, held in limits.items() if not held] == failed, rows
    assert ok is (not failed)
    if fault == "state_bf16":
        assert rows[-1][1] == 1.0 and rows[-2][1] < 0.03 * rows[-2][2]
    if fault is None:
        assert rows[-1][1] < 1e-3


def test_the_configuration_file_is_the_catalogs_row_cut_as_it_says():
    """Every published width and all eleven multipliers as published, the
    depth alone reduced; the program's tree at the cell's size (by shapes)
    counts what ``opcount_falcon.num_params`` and the file's text count."""
    import sys
    sys.path.insert(0, ROOT)
    from benchmarks import common, opcount_falcon

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "falcon-h1-34b-l4.json")) as f:
        config = json.load(f)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["intermediate_size"], config["vocab_size"]) \
        == (5120, 20, 4, 128, 21504, 261120)
    assert (config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_ssm"], config["mamba_d_state"],
            config["mamba_n_groups"], config["mamba_d_conv"],
            config["mamba_chunk_size"]) == (32, 128, 4096, 256, 2, 4, 128)
    assert config["mamba_d_ssm"] != config["mamba_expand"] \
        * config["hidden_size"]
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["reduced_from"] == {"num_hidden_layers": 72}
    for key in ("embedding_multiplier", "key_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "ssm_in_multiplier", "ssm_out_multiplier", "ssm_multipliers",
                "mlp_multipliers", "lm_head_multiplier"):
        assert key in config["model_keys"] and config[key] == TOY[key], key
    m = common.model_section(config)
    cfg = Config.from_dict({
        "distributed": {"use_cpu": True}, "model": m,
        "training": {"seq_length": 6144}, "dataset": {"name": "synthetic"}})
    n = fh.num_params(cfg.model)
    assert n == opcount_falcon.num_params(config) == 4_394_354_048
    assert f"{n:,}" in config["deployment"]
    # a program without the block: the first key ModelConfig lacks, exit 2
    config["model_keys"] = config["model_keys"] + ["mamba_d_mystery"]
    config["mamba_d_mystery"] = 1
    with pytest.raises(SystemExit) as e:
        common.model_section(config)
    assert e.value.code == 2
