"""The MiMo-V2 block (models/mimo_v2.py) on the serving path, at toy size in
float32 on the CPU with seeded weights, against the plain reference
(benchmarks/reference/mimo_v2.py): the engine's programs through a cache of
four leaves (full-length rows of 2 K/V heads, rings of 4 that a sliding layer
writes at ``pos mod ring``, keys of 24 beside values of 16) with the toy
window smaller than the chunk and the ring wrapping five times, the sink, the
partial rotation under two bases, what each kind of layer sees, the expert
share with no shared expert, the new forms of the stacked decode kernel, and
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
from engine_memo import (
    admit,
    decode,
    memoized,
    program_logits,
    worst_rel_err,
)

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.inference import InferenceEngine, kv_cache
from picotron_tpu.models import afmoe, experts, mimo_v2
from picotron_tpu.ops.rope import apply_rope, apply_rope_leading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = "mimo-v2.5-ep32-l13"
CELL = NAME + ".serve-mixedctx-decode"

# what is new is kept: K/V heads that differ by kind (2 full, 4 sliding),
# keys wider than values (24, 16), 8 of a head's 24 dimensions rotated, two
# bases, a window (6) smaller than the chunk (8)
TOY = block_toys.TOYS["mimo_v2"]


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_mimo_v2",
        os.path.join(ROOT, "benchmarks", "reference", "mimo_v2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


make_config = partial(block_toys.make_config, "mimo_v2")


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, slots=2, max_seq_len=128,
                             **{"prefill_chunk": 8, **kw})
    params = jax.jit(lambda k: mimo_v2.init_params(k, cfg.model))(
        jax.random.PRNGKey(7))
    return cfg, engine, engine.shard_params(params)


def reference_rows(params, seq, n_prompt, model=TOY):
    return ref.forward_logits(params, np.asarray([seq], np.int32),
                              dict(model), jax.devices()[0])[0][n_prompt - 1:]


RNG = np.random.default_rng(3)
PROMPT = [int(t) for t in RNG.integers(1, 256, 70)]
OTHER = [int(t) for t in RNG.integers(1, 256, 70)]


@pytest.fixture
def toy():
    return make_engine()


def one_layer(group: str, seed: int = 2):
    """(cfg, one layer's leaves of ``group``) at toy size."""
    cfg = make_config()
    stack = jax.jit(lambda k: mimo_v2.init_params(k, cfg.model))(
        jax.random.PRNGKey(seed))[group]
    return cfg, {n: v[0] for n, v in stack.items()}


# ---- (a) the engine's programs against the reference ----------------------


@pytest.mark.parametrize("n_prompt,chunk,steps", [
    # ring 14 < prompt: 8 3/4 chunks (the last with two pad rows), the ring
    # written round five times, decode steps across pos 70 = 5 x 14
    (70, 8, 6),
    (6, 8, 4),     # one-shot, its bucket of 16 rows cut to a chunk's 8
    (41, 16, 4),   # ring 22: a chunk of 16 over a window of 6
])
def test_prefill_and_decode_match_the_reference(n_prompt, chunk, steps):
    _, engine, params = make_engine(prefill_chunk=chunk)
    seq, got, cache = program_logits(engine, params, PROMPT[:n_prompt],
                                     steps=steps)
    assert worst_rel_err(got, reference_rows(params, seq, n_prompt)) < 1e-4
    assert int(cache["lengths"][0]) == n_prompt + steps
    assert cache["kw"].shape[2] == 6 + chunk


def test_the_whole_forward_matches_the_reference_at_every_position(toy):
    """The one-shot program's layer functions over a whole sequence longer
    than the window (no cache): every position's logits, and the counts."""
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
    rows = dict(zip(mimo_v2.STAT_NAMES, np.asarray(stats).T))
    # sliding layers 1, 2 and 4: 1 + 2 + .. of the first 6 queries, then 6
    # each; the full ones count nothing
    attended = sum(min(t + 1, 6) for t in range(50))
    assert list(rows["swa_rows_attended"]) == [0, attended, attended, 0, attended]
    assert list(rows["swa_rows_context"]) == [0, 1275, 1275, 0, 1275]  # 50 x 51 / 2
    assert list(rows["moe_layer_steps"]) == [0, 1, 1, 1, 1]
    assert not rows["swa_layer_steps"].any()


def test_a_slot_used_twice_forgets_its_first_occupant(toy):
    _, engine, params = toy
    _, _, cache = program_logits(engine, params, PROMPT, steps=3)
    cache = engine.release(cache, 0)
    _, again, _ = program_logits(engine, params, OTHER[:13], cache=cache)
    _, fresh, _ = program_logits(engine, params, OTHER[:13])
    np.testing.assert_allclose(np.stack(again), np.stack(fresh), atol=1e-6)


def test_a_decode_block_is_the_steps_one_by_one_across_a_wrap(toy):
    """Eight steps in one program from pos 70 (rows 0, 1, ..: 70 = 5 x 14):
    the tokens the single steps give, a slot out of budget left where it
    was."""
    _, engine, params = toy
    cache = engine.init_cache()
    cache, last = admit(engine, params, cache, PROMPT, slot=0)
    cache, _ = admit(engine, params, cache, OTHER[:30], slot=1)
    first = int(np.argmax(last))
    want, tok = [], first
    solo = jax.tree.map(jnp.copy, cache)
    for _ in range(8):
        solo, logits = decode(engine, params, solo, tok, slot=0)
        tok = int(np.argmax(logits))
        want.append(tok)
    out = engine.decode_block(
        params, cache, np.asarray([first, 5], np.int32),
        jax.random.split(jax.random.PRNGKey(0), engine.decode_block_len),
        np.asarray([-1, -1], np.int32), np.asarray([8, 0], np.int32),
        np.zeros(2, np.float32), np.zeros(2, np.int32),
        np.ones(2, np.float32))
    tokens, counts, _ = out.host()
    assert tokens[0].tolist() == want and counts.tolist() == [8, 0]
    assert np.asarray(out.cache["lengths"]).tolist() == [78, 30]


# ---- (b) the sink, the rotation, the heads, the window --------------------


def softmax_rows(q, k, seen, sink):
    """What ``masked_attention`` weighs each key with, read off values that
    are the identity: [B, S, heads, T]."""
    T = k.shape[1]
    eye = jnp.broadcast_to(jnp.eye(T)[None, :, None, :],
                           (k.shape[0], T, k.shape[2], T))
    return afmoe.masked_attention(q, k, eye, seen, 24 ** -0.5, sink)


def test_the_sink_takes_its_share_and_has_no_value():
    """A sliding layer's rows sum to less than 1 by the sink's share, a full
    layer's to 1, and a sink of -inf is the plain softmax."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, 5, 8, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 5, 4, 24)), jnp.float32)
    pos = jnp.arange(5)[None]
    seen = afmoe.visible(pos, pos, 3)
    sink = jnp.asarray(rng.standard_normal(8), jnp.float32)
    plain = softmax_rows(q, k, seen, None)
    np.testing.assert_allclose(plain.sum(-1), 1.0, atol=1e-6)
    with_sink = softmax_rows(q, k, seen, sink)
    z = jnp.einsum("bskgd,btkd->bskgt", q.reshape(1, 5, 4, 2, 24), k) \
        .reshape(1, 5, 8, 5) * 24 ** -0.5
    z = jnp.where(seen[:, :, None, :], z, -jnp.inf)
    share = jax.nn.softmax(jnp.concatenate(
        [z, jnp.broadcast_to(sink[None, None, :, None], (1, 5, 8, 1))], -1),
        axis=-1)
    np.testing.assert_allclose(with_sink, share[..., :-1], atol=1e-6)
    np.testing.assert_allclose(1.0 - with_sink.sum(-1), share[..., -1],
                               atol=1e-6)
    assert float(share[..., -1].min()) > 0.01
    # the weights of the keys keep their proportions
    np.testing.assert_allclose(
        with_sink / with_sink.sum(-1, keepdims=True), plain, atol=1e-6)
    gone = softmax_rows(q, k, seen, jnp.full(8, -jnp.inf, jnp.float32))
    np.testing.assert_allclose(gone, plain, atol=1e-7)


@pytest.mark.parametrize("kind", ["chunk", "decode"])
def test_the_sink_in_the_cached_paths_is_the_sink_of_the_plain_one(kind):
    """The chunk's walk (a running softmax that starts from the sink) and
    the decode step's contraction against ``masked_attention`` over the
    same keys, on a ring that has come round."""
    rng = np.random.default_rng(1)
    T, W, nkv, nh = 16, 5, 4, 8
    kw = jnp.asarray(rng.standard_normal((2, 2, T, nkv * 24)), jnp.float32)
    vw = jnp.asarray(rng.standard_normal((2, 2, T, nkv * 16)), jnp.float32)
    sink = jnp.asarray(rng.standard_normal(nh), jnp.float32)
    if kind == "chunk":
        pos_q = jnp.arange(20, 24)[None]  # rows 4-7 of the ring
        q = jnp.asarray(rng.standard_normal((1, 4, nh, 24)), jnp.float32)
        got = afmoe.chunk_attention(q, kw, vw, 1, jnp.int32(1), pos_q, W,
                                    0.2, sink=sink, kv_heads=nkv)
        pos_k = afmoe.ring_positions(pos_q[:, -1], T)
        k, v = kw[1, 1:], vw[1, 1:]
    else:
        pos = jnp.asarray([37, 3], jnp.int32)
        pos_q = pos[:, None]
        q = jnp.asarray(rng.standard_normal((2, 1, nh, 24)), jnp.float32)
        got = mimo_v2.decode_attend(q, kw, vw, pos, 1, W, 0.2, sink, nkv)
        pos_k = afmoe.ring_positions(pos, T)
        k, v = kw[1], vw[1]
    want = afmoe.masked_attention(
        q, k.reshape(-1, T, nkv, 24), v.reshape(-1, T, nkv, 16),
        afmoe.visible(pos_q, pos_k, W), 0.2, sink)
    assert got.shape == q.shape[:3] + (16,)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_a_third_of_each_head_is_rotated_under_two_bases():
    m = make_config().model
    assert mimo_v2.rotated_dims(m) == 8  # int(24 x 0.334)
    cos, sin = mimo_v2.serving_rope_tables(m, 32, jnp.float32)
    assert cos.shape == sin.shape == (32, 16)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 32, 3, 24))
    for window, base in ((False, 1e7), (True, 1e4)):
        c, s = mimo_v2._own_tables(cos, sin, window)
        got = apply_rope_leading(x, c, s)
        # the other sixteen dimensions are untouched
        np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
        inv = 1.0 / base ** (np.arange(0, 8, 2) / 8)
        ang = np.arange(32)[:, None] * inv[None]
        want_cos = np.concatenate([np.cos(ang), np.cos(ang)], -1)
        np.testing.assert_allclose(c, want_cos, atol=1e-6)
        np.testing.assert_allclose(got[..., :8],
                                   apply_rope(x[..., :8], c, s), atol=1e-7)
        # and the reference's rotation is the same one
        np.testing.assert_allclose(got[0], ref._rope(x[0], base, 8),
                                   atol=1e-5)
    full, ring = (mimo_v2._own_tables(cos, sin, w)[0] for w in (False, True))
    assert float(jnp.max(jnp.abs(full[5] - ring[5]))) > 0.1  # two bases
    # a whole head: the plain rotation
    c24 = jnp.ones((32, 24))
    np.testing.assert_array_equal(apply_rope_leading(x, c24, 0 * c24), x)


@pytest.mark.parametrize("window", [True, False])
def test_each_kind_of_layer_has_its_own_heads(window):
    """A sliding layer's K and V have 4 heads, a full layer's 2, keys of 24
    and values of 16 in both, and each writes rows of its own leaves."""
    cfg, lp = one_layer("moe_window_1" if window else "moe_full_2")
    m = cfg.model
    assert mimo_v2.heads(m, window) == ((8, 4, 24, 16) if window
                                        else (8, 2, 24, 16))
    assert lp["wk"].shape == (64, (4 if window else 2) * 24)
    assert lp["wv"].shape == (64, (4 if window else 2) * 16)
    assert lp["wq"].shape == (64, 8 * 24) and lp["wo"].shape == (8 * 16, 64)
    assert ("sink" in lp) == window
    cache = mimo_v2.init_cache(m, 2, 32, prefill_chunk=8)
    assert {n: v.shape for n, v in cache.items()} == {
        "k": (2, 2, 32, 48), "v": (2, 2, 32, 32), "kw": (3, 2, 14, 96),
        "vw": (3, 2, 14, 64), "lengths": (2,)}
    cos, sin = mimo_v2.serving_rope_tables(m, 32, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 64), jnp.float32)
    pos = jnp.asarray([3, 17], jnp.int32)
    _, out = mimo_v2._layer(lp, h, cos[pos][:, None], sin[pos][:, None], cfg,
                            cache=cache, pos=pos, layer=jnp.int32(0),
                            dense=False, window=window)
    mine, other = (("kw", "vw"), ("k", "v")) if window else \
        (("k", "v"), ("kw", "vw"))
    for n in other:
        assert not np.asarray(out[n]).any()
    at = [3, 17 % 14] if window else [3, 17]
    for n in mine:
        leaf = np.asarray(out[n])
        assert all(leaf[0, b, at[b]].any() for b in (0, 1))
        assert np.count_nonzero(leaf.any(-1)) == 2


@pytest.mark.parametrize("window", [True, False])
def test_a_sliding_layer_forgets_what_a_full_layer_remembers(window):
    cfg, lp = one_layer("moe_window_1" if window else "moe_full_2")
    T = 40
    cos, sin = mimo_v2.serving_rope_tables(cfg.model, T, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(3), (1, T, 64), jnp.float32)
    # everything at or before T - 1 - window changes
    other = h.at[:, :T - 6].set(
        jax.random.normal(jax.random.PRNGKey(4), (1, T - 6, 64)))

    @jax.jit
    def branch(x):
        out, _ = mimo_v2._layer(lp, x, cos, sin, cfg, dense=False,
                                window=window)
        return out - x

    a, b = (np.asarray(branch(x)[0, -1]) for x in (h, other))
    if window:
        np.testing.assert_allclose(a, b, atol=1e-6)
    else:
        assert np.max(np.abs(a - b)) > 1e-2


# ---- (c) the new forms of the stacked decode kernel -------------------------


def dense_answer(q, k, v, pos, nkv, window, sink):
    """``kv_cache.decode_attention``'s answer where it has one (a prefix,
    no sink), else the masked contraction of the layer's block."""
    B, T = k.shape[0], k.shape[1]
    k4, v4 = k.reshape(B, T, nkv, -1), v.reshape(B, T, nkv, -1)
    if not window and sink is None:
        vpad = jnp.pad(v4, ((0, 0),) * 3 + ((0, k4.shape[-1] - v4.shape[-1]),))
        return kv_cache.decode_attention(
            q, k4, vpad, pos + 1, q.shape[-1] ** -0.5)[..., :v4.shape[-1]]
    pos_k = afmoe.ring_positions(pos, T) if window else \
        jnp.broadcast_to(jnp.arange(T), (B, T))
    return afmoe.masked_attention(
        q, k4, v4, afmoe.visible(pos[:, None], pos_k, window),
        q.shape[-1] ** -0.5, sink)


# a ring of five blocks under a window of one (T 80, W 16, blocks of 16): a
# window not full (0, 7), a ring not come round (40), the query's row a
# block's last (47, 79) and first (64: the window ends in the next block's
# row 0), a window that wraps from the last block to block 0 (85), one that
# is block 0 whole (95), come round twice (160: row 0; 197)
SMALL_WINDOW_POS = [0, 7, 40, 47, 64, 79, 85, 95, 160, 197]


@pytest.mark.parametrize("form,block_t,T,W", [
    ("full", 16, 48, 32), ("full", None, 48, 32), ("ring_sink", 16, 48, 32),
    ("ring_sink", 48, 48, 32), ("ring", 16, 48, 32),
    ("full_sink", 16, 48, 32), ("ring_sink", 16, 80, 16),
    ("ring_sink", None, 80, 16), ("ring", 16, 80, 16),
    ("ring", None, 80, 16), ("ring", None, 48, 32)])
def test_the_stacked_kernel_with_keys_wider_than_values(form, block_t, T, W):
    """``flash_decode_stacked`` in interpret mode, a row's heads merged (K
    rows of 4 x 192, V rows of 4 x 128; 16 query heads), against the dense
    answer: a prefix, and a ring (not come round, come round once and
    twice, a dead block; and a ring of five blocks under a window of one:
    ``SMALL_WINDOW_POS``) with and without a sink; a sink of -inf gives the
    plain form's answer."""
    from picotron_tpu.ops.pallas.decode_attention import flash_decode_stacked

    rng = np.random.default_rng(0)
    window = W if form.startswith("ring") else 0
    pos = jnp.asarray(([0, 7, 47, 63, 88, 127] if T == 48
                       else SMALL_WINDOW_POS) if window
                      else [0, 7, 15, 16, 33, 47], jnp.int32)
    L, B, nkv, D, Dv, nh = 3, len(pos), 4, 192, 128, 16
    k = jnp.asarray(rng.standard_normal((L, B, T, nkv * D)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((L, B, T, nkv * Dv)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, 1, nh, D)), jnp.bfloat16)
    sink = jnp.asarray(2.0 + rng.standard_normal(nh), jnp.float32) \
        if form.endswith("sink") else None

    def run(sink):
        return flash_decode_stacked(
            q, k[:, :, :, None], v[:, :, :, None], pos + 1, D ** -0.5, 1,
            block_t=block_t, interpret=True, window=window or None,
            sink=sink)

    got = run(sink)
    assert got.shape == (B, 1, nh, Dv) and got.dtype == jnp.bfloat16
    want = dense_answer(q, k[1], v[1], pos, nkv, window, sink)
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                 - want.astype(jnp.float32)))) < 2e-2
    if sink is not None:
        plain = dense_answer(q, k[1], v[1], pos, nkv, window, None)
        # the sink is heard: it takes mass from every row
        assert float(jnp.max(jnp.abs(want.astype(jnp.float32)
                                     - plain.astype(jnp.float32)))) > 5e-2
        gone = run(jnp.full(nh, -jnp.inf, jnp.float32))
        assert float(jnp.max(jnp.abs(gone.astype(jnp.float32)
                                     - plain.astype(jnp.float32)))) < 2e-2


def test_on_a_tpu_the_decode_step_takes_the_kernel(monkeypatch):
    """``decode_attend`` routes bfloat16 rows of whole lanes to the kernel
    where ``on_tpu`` says so (the ring form with its sink for a window), and
    the float32 toy or a dense engine to the contraction."""
    from picotron_tpu.ops.pallas import decode_attention as da

    calls = []
    monkeypatch.setattr(mimo_v2, "on_tpu", lambda: True)
    monkeypatch.setattr(
        da, "flash_decode_stacked",
        lambda q, k, v, lengths, scale, layer, window, sink:
        calls.append((k.shape, v.shape, lengths.tolist(), window,
                      sink is not None)) or q[..., :128])
    kw = jnp.zeros((2, 3, 640, 1536), jnp.bfloat16)
    vw = jnp.zeros((2, 3, 640, 1024), jnp.bfloat16)
    q = jnp.zeros((3, 1, 64, 192), jnp.bfloat16)
    pos = jnp.asarray([0, 700, 5], jnp.int32)
    sink = jnp.zeros(64, jnp.float32)
    mimo_v2.decode_attend(q, kw, vw, pos, 1, 128, 0.1, sink, 8)
    mimo_v2.decode_attend(q, kw[..., :768], vw[..., :512], pos, 1, 0, 0.1,
                          None, 4)
    assert calls == [
        ((2, 3, 640, 1, 1536), (2, 3, 640, 1, 1024), [1, 701, 6], 128, True),
        ((2, 3, 640, 1, 768), (2, 3, 640, 1, 512), [1, 701, 6], None,
         False)]
    f32 = lambda x: x.astype(jnp.float32)
    mimo_v2.decode_attend(f32(q), f32(kw), f32(vw), pos, 1, 128, 0.1, sink, 8)
    mimo_v2.decode_attend(q, kw, vw, pos, 1, 128, 0.1, sink, 8, impl="dense")
    assert len(calls) == 2
    # the kernel's own choice of block: the cells' rows as they were, a
    # row of 768 lanes in the largest divisor under 1 MiB of K; a ring's
    # no larger than its window (this cell's 640 rows in five blocks of
    # 128, Trinity's 4,608 under a window of 4,096 in the nine of 512 it had)
    assert da._stacked_block_rows(16384, 2 * 768) == 512
    assert da._ring_block_rows(640, 2 * 1536, 128) == 128
    assert da._ring_block_rows(4608, 2 * 8 * 128, 4096) == 512
    assert [da._ring_walk(W, b, T // b) for W, b, T in (
        (128, 128, 640), (4096, 512, 4608), (16, 16, 80), (32, 16, 48),
        (4096, 512, 2048), (1, 16, 48))] == [2, 9, 2, 3, 4, 1]
    assert [da._stacked_block_rows(T, 2 * rows * 128) for T, rows in (
        (2048, 16), (2048, 8), (32768, 8), (4608, 8), (4096, 8), (48, 2))] \
        == [256, 512, 512, 512, 512, 48]


@pytest.mark.parametrize("T,W,block_t", [
    (80, 16, 16), (48, 32, 16), (48, 32, 8), (640, 128, 128), (640, 128, 64),
    (4608, 4096, 512), (2048, 4096, 512), (96, 96, 32), (64, 1, 16)])
def test_the_ring_walk_names_the_blocks_the_query_sees(T, W, block_t):
    """``_ring_blocks`` alone: for every length from 1 to three times round
    the ring, the blocks it names are exactly those that hold a row
    ``rings.visible`` marks, each once and never more than the static bound
    the grid is built to; a free slot walks none. (A ring shorter than its
    window, which a short ``max_seq_len`` makes, never comes round.)"""
    from picotron_tpu.ops.pallas import decode_attention as da

    nb = T // block_t
    steps = da._ring_walk(W, block_t, nb)
    assert steps <= nb
    L = jnp.arange(0, (3 * T if W <= T else T) + 1, dtype=jnp.int32)
    first, count = da._ring_blocks(jnp.minimum(L, T), (L - 1) % T, window=W,
                                   block_t=block_t, max_nb=nb)
    first, count = np.asarray(first), np.asarray(count)
    assert count[0] == 0 and 0 <= first.min() and first.max() < nb
    assert count[1:].min() >= 1 and count.max() == steps
    walked = (first[:, None] + np.arange(nb)) % nb  # [lengths, steps]
    named = np.zeros((len(L), nb), int)
    np.add.at(named, (np.arange(len(L))[:, None], walked),
              np.arange(nb) < count[:, None])
    pos = L[1:] - 1
    seen = afmoe.visible(pos[:, None], afmoe.ring_positions(pos, T), W)[:, 0]
    holds = np.asarray(seen).reshape(len(pos), nb, block_t).any(-1)
    np.testing.assert_array_equal(named[1:], holds.astype(int))


# ---- (d) the share and the router -------------------------------------------


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Ranks 0-3 of 4, two experts each, and no shared expert to count once,
    against the uncut layer of eight, and against the reference's."""
    uncut = make_config(dict(n_routed_experts=8, ep_size=1)).model
    group = jax.jit(lambda k: mimo_v2.init_params(k, uncut))(
        jax.random.PRNGKey(5))["moe_window_1"]
    lp = {n: v[0] for n, v in group.items()}
    assert "ws_gate" not in lp
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64), jnp.float32)
    live = jnp.ones((2, 12), bool)

    def mlp(lp, x, m, live):
        return jax.jit(lambda lp, x, live: mimo_v2.expert_mlp(
            lp, x, m, live))(lp, x, live)

    whole, (assigned, hit, *_) = mlp(lp, x, uncut, live)
    assert int(assigned) == 2 * 12 * 2 and int(hit) <= 8
    total, held = jnp.zeros_like(whole), 0
    for rank in range(4):
        m = make_config(dict(ep_rank=rank)).model
        part = {**lp, **{n: lp[n][2 * rank:2 * rank + 2]
                         for n in ("w1", "w3", "w2")}}
        y, (n, *_) = mlp(part, x, m, live)
        total, held = total + y, held + int(n)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert held == 2 * 12 * 2  # every token's experts are held by some rank
    want = ref.experts(lp, x.reshape(24, 64),
                       dict(TOY, n_routed_experts=8, ep_size=1))
    np.testing.assert_allclose(whole.reshape(24, 64), want, atol=2e-5)
    # rows that are not live are routed nowhere: nothing at all
    y, (n, *_) = mlp(lp, x, uncut, jnp.zeros((2, 12), bool))
    assert not np.asarray(y).any() and int(n) == 0


def test_share_with_a_shared_expert_is_bit_for_bit_what_it_was():
    """``experts.share``: without ``ws_gate`` the routed sum alone; with it,
    the sum the three other blocks have always had."""
    rng = np.random.default_rng(2)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.1, jnp.bfloat16)
    lp = {"w1": f(2, 16, 8), "w3": f(2, 16, 8), "w2": f(2, 8, 16)}
    shared = {"ws_gate": f(16, 8), "ws_up": f(16, 8), "ws_down": f(8, 16)}
    x = f(6, 16)
    w_held = jnp.asarray(rng.uniform(0, 1, (6, 2)) * (rng.uniform(
        0, 1, (6, 2)) > 0.4), jnp.float32)
    routed, run, pipelined = experts.routed_experts(x, w_held, lp)
    y, (assigned, hit, steps, rows, passed) = experts.share(lp, x, w_held)
    assert y.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(routed.astype(x.dtype),
                                             np.float32))
    ys, (assigned_s, hit_s, *_) = experts.share({**lp, **shared}, x, w_held)
    before = routed.astype(x.dtype) + experts.swiglu(
        x, shared["ws_gate"], shared["ws_up"], shared["ws_down"])
    np.testing.assert_array_equal(np.asarray(ys, np.float32),
                                  np.asarray(before, np.float32))
    assert int(assigned) == int(assigned_s) == int((w_held > 0).sum())
    assert int(hit) == int(hit_s) and int(steps) == 1
    # below the ridge, the pass or the loop: every row, both experts
    assert int(rows) == int(run) == 6 * 2
    assert int(passed) == int(pipelined) == 1


def test_the_router_is_experts_route_with_eight_of_256():
    m = make_config().model
    assert (m.routed_scaling_factor, mimo_v2.ROUTE_EPS) == (1.0, 1e-20)
    scores = jnp.asarray([[0.875, 0.75, 0.125, 0.9375, 0.5625, 0.5,
                           0.6875, 0.625]])
    bias = jnp.zeros(8).at[5].set(0.4)  # 0.9 biased: between the two
    chosen, w = experts.route(scores, bias, k=2, scale=1.0,
                              eps=mimo_v2.ROUTE_EPS)
    assert chosen.tolist() == [[3, 5]]
    np.testing.assert_allclose(w[0], np.array([0.9375, 0.5]) / 1.4375,
                               rtol=1e-6)
    logit = jnp.log(scores / (1 - scores))
    r_chosen, r_w = ref.route(logit, jnp.eye(8), bias, k=2, scale=1.0)
    assert r_chosen.tolist() == chosen.tolist()
    np.testing.assert_allclose(r_w, w, rtol=1e-5)


# ---- (e) groups, cache, counts ----------------------------------------------


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
    groups = mimo_v2.layer_groups(m)
    assert [(n, c) for n, _, c in groups] == [
        ("dense_full_0", 1), ("moe_window_1", 2), ("moe_full_2", 1),
        ("moe_window_3", 1)]
    assert [fn.keywords["kind_first"] for _, fn, _ in groups] == [0, 0, 1, 2]
    real = mimo_v2.layer_groups(published_model())
    assert [(n, c) for n, _, c in real] == [
        ("dense_full_0", 1), ("moe_window_1", 5), ("moe_full_2", 1),
        ("moe_window_3", 5), ("moe_full_4", 1)]
    assert [fn.keywords["kind_first"] for _, fn, _ in real] \
        == [0, 0, 1, 5, 2]


def test_the_cache_holds_four_leaves_of_four_shapes_at_the_cells_sizes():
    """32 slots x 16,384: 16,384 rows of 4 x 192 and 4 x 128 a slot in the
    three full layers, 640 rows of 8 x 192 and 8 x 128 in the ten sliding
    ones: 5.08 GB, where thirteen full-length layers would be 30.9."""
    m = published_model()
    shapes = jax.eval_shape(lambda: mimo_v2.init_cache(
        m, 32, 16384, prefill_chunk=512))
    assert shapes["k"].shape == (3, 32, 16384, 4 * 192)
    assert shapes["v"].shape == (3, 32, 16384, 4 * 128)
    assert shapes["kw"].shape == (10, 32, 640, 8 * 192)
    assert shapes["vw"].shape == (10, 32, 640, 8 * 128)
    assert all(shapes[n].dtype == jnp.bfloat16 for n in mimo_v2.LEAVES)
    # every row whole lanes: no key head padded from 192 to 256
    assert all(shapes[n].shape[-1] % 128 == 0 for n in mimo_v2.LEAVES)
    assert round(kv_cache.cache_bytes(shapes) / 1e9, 2) == 5.08
    assert round((3 * 2560 + 10 * 5120) * 32 * 16384 / 1e9, 1) == 30.9
    assert set(mimo_v2.cache_pspecs(m)) == set(shapes)
    assert afmoe.ring_rows(m, 16384, 512) == 640


def test_parameters_are_the_opcounts():
    sys.path.insert(0, ROOT)
    from benchmarks import opcount_mimo as om

    pub = published_config()
    assert mimo_v2.num_params(published_model()) == om.num_params(pub) \
        == 3_997_286_016
    assert round(2 * om.num_params(pub) / 1e9, 2) == 7.99
    assert om.layer_params(pub, False, False) == 290_463_744
    assert om.layer_params(pub, True, True) == 296_755_520
    assert om.layer_params(pub, False, True) == 291_512_576
    assert om.attention_params(pub, False) == 89_128_960
    assert om.attention_params(pub, True) == 94_371_904
    assert om.cache_bytes(pub, 32, 16384, 512) == (4_026_531_840,
                                                   1_048_576_000)
    assert (om.kv_bytes_per_row(pub, False), om.kv_bytes_per_row(pub, True)) \
        == (2560, 5120)
    toy_m = make_config().model
    p = jax.eval_shape(lambda: mimo_v2.init_params(jax.random.PRNGKey(0),
                                                   toy_m))
    assert mimo_v2.num_params(toy_m) == sum(
        v.size for v in jax.tree.leaves(p)) == om.num_params(
            dict(TOY, torch_dtype="float32"))
    # a step of slots at 100 and 5,000: the weights but the embedding,
    # 5,100 rows in each full layer, 100 + 128 in each sliding one
    got = om.decode_step_bytes(pub, [100, 5000])
    assert got == 2 * (3_997_286_016 - 19072 * 4096) \
        + 3 * 5100 * 2560 + 10 * 228 * 5120
    assert om.window_attend_bytes(pub, [100, 5000]) == 228 * 5120
    assert om.full_attend_bytes(pub, [100, 5000]) == 5100 * 2560


def test_seeded_draws_are_as_the_configuration_file_says(toy):
    cfg, _, params = toy
    g = params["moe_window_1"]
    assert g["router_bias"].dtype == g["sink"].dtype == jnp.float32
    bias = np.abs(np.asarray(g["router_bias"]))
    assert 0 < bias.max() <= mimo_v2.ROUTER_BIAS and bias.min() > 0
    sinks = np.asarray(g["sink"])
    assert sinks.shape == (2, 8)
    assert abs(sinks.mean() - np.log(6)) < 1.0 and sinks.std() > 0.3
    bound = (1 / 128) ** 0.5 * mimo_v2.INIT_GAIN["wo"]
    assert 0.9 * bound < np.abs(np.asarray(g["wo"])).max() <= bound
    bound = (1 / 32) ** 0.5 * mimo_v2.INIT_GAIN["w2"]
    assert 0.9 * bound < np.abs(np.asarray(g["w2"])).max() <= bound
    assert "sink" not in params["moe_full_2"] \
        and "router" not in params["dense_full_0"]
    assert params["lm_head"].shape == (64, 256)


# ---- (f) refused by name ----------------------------------------------------


def test_training_is_refused_by_name():
    cfg = make_config()
    with pytest.raises(ValueError, match="mimo_v2.*served, not trained"):
        cfg.validate(for_training=True)


@pytest.mark.parametrize("model,match", [
    ({"hybrid_layer_pattern": [1] * 5}, "at least one full .0. and one"),
    ({"hybrid_layer_pattern": [0, 1]}, "for each of the 5 layers"),
    ({"hybrid_layer_pattern": [0, 1, 2, 0, 1]}, "hybrid_layer_pattern"),
    ({"moe_layer_freq": 1}, "moe_layer_freq: 0 or 1 for each"),
    ({"sliding_window": 0}, "sliding_window >= 1"),
    ({"swa_num_key_value_heads": 3}, "sliding layers' 8 query heads"),
    ({"partial_rotary_factor": 0.3}, "rotates 7 dimensions"),
    ({"swa_v_head_dim": 8}, "hand W_o as many columns"),
    ({"n_group": 2}, "n_group = 1 only"),
    ({"scoring_func": "softmax"}, "scoring_func = 'sigmoid' only"),
    ({"n_shared_experts": 1}, "n_shared_experts = 0 only"),
    ({"add_swa_attention_sink_bias": False},
     "add_swa_attention_sink_bias = True only"),
    ({"add_full_attention_sink_bias": True},
     "add_full_attention_sink_bias = False only"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings = False only"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}},
     "rope_scaling of type 'default'"),
    ({"layernorm_epsilon": 1e-6}, "layernorm_epsilon 1e-06 is not"),
    ({"ep_rank": 4}, "ep_rank 4 outside"),
    ({"num_experts_per_tok": 9}, "passes the router's width 8"),
    ({"first_layer": 9, "total_layers": 12}, "lie outside total_layers 12"),
])
def test_validate_refuses_what_the_block_lacks(model, match):
    with pytest.raises(ValueError, match="mimo_v2.*" + match):
        make_config(model)


def test_the_published_keys_reach_the_program_under_their_names():
    m = published_model()
    assert (m.first_layer, m.total_layers) == (6, 48)
    assert (m.head_dim, m.v_head_dim, m.swa_head_dim, m.swa_v_head_dim) \
        == (192, 128, 192, 128)
    assert (m.num_key_value_heads, m.swa_num_key_value_heads) == (4, 8)
    assert mimo_v2.rotated_dims(m) == 64
    assert (m.rope_theta, m.swa_rope_theta) == (1e7, 1e4)
    assert m.layernorm_epsilon == m.rms_norm_eps == 1e-5
    assert m.rope_scaling == {"rope_type": "default", "type": "default"}
    assert isinstance(m.moe_layer_freq, list)
    assert ModelConfig().moe_layer_freq == 1  # deepseek_v32's int
    Config.from_dict({"model": dict(vars(m), _head_dim=None,
                                    head_dim=192)}).validate()


def test_engine_keywords_are_refused_too():
    cfg = make_config()
    with pytest.raises(ValueError, match="mimo_v2.*speculation"):
        InferenceEngine(cfg, slots=2, max_seq_len=128, spec_len=2)
    with pytest.raises(ValueError, match="mimo_v2.*kv_layout 'paged'"):
        InferenceEngine(cfg, slots=2, max_seq_len=128, kv_layout="paged")


# ---- (g) the controls, the counters -----------------------------------------


def test_bfloat16_fails_the_float32_check(toy):
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


@pytest.mark.parametrize("fault", ["sink_left_out", "window_ignored",
                                   "ring_a_window_short"])
def test_a_fault_in_the_sliding_layers_fails_the_check(fault, monkeypatch,
                                                       toy):
    """The three controls the cell's ``correct`` must see, at toy size, in
    the chunked prefill's logits (benchmarks/tests/control_mimo.py runs them
    and six more on the chip)."""
    sys.path.insert(0, ROOT)
    from benchmarks.tests import control_mimo

    _, _, params = toy
    want = reference_rows(params, PROMPT, len(PROMPT))
    with control_mimo.fault(fault):
        _, engine, _ = make_engine(fresh=True)  # traced under the fault
        _, last = admit(engine, params, engine.init_cache(), PROMPT)
    assert worst_rel_err([last], want) > 1e-2
    assert mimo_v2.rings.visible is afmoe.visible  # taken away again


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
    for name in mimo_v2.STAT_NAMES:
        line, = [l for l in text.splitlines()
                 if l.startswith(f"picotron_{name}_total ")]
        got[name] = float(line.split()[1])

    def rows(n, cap):  # a prompt's queries, then its four decode steps
        return sum(min(t + 1, cap) for t in range(n + 4))

    # three sliding layers
    assert got["swa_rows_context"] == 3 * sum(
        rows(n, 10 ** 6) for n in (44, 6, 20))
    assert got["swa_rows_attended"] == 3 * sum(
        rows(n, 6) for n in (44, 6, 20))
    assert got["swa_layer_steps"] % 3 == 0 and got["swa_layer_steps"] >= 3 * 8
    assert got["moe_layer_steps"] > 0 and got["moe_assignments"] > 0
    alone = ContinuousBatcher(shared, params, seed=0).run(
        [Request(uid="x", prompt=OTHER[:20], max_new_tokens=5)])
    assert out["r2"].tokens == alone["x"].tokens


# ---- (h) the cell ------------------------------------------------------------


def test_rehearsal_of_the_cell_computes_its_readers():
    """The cell's control flow at toy size on the CPU, led in for 3 s and
    measured for 4 s (as PR 39 steadied its twin against six workers)."""
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
            "moe.held_assignments_per_step.mimo"} <= set(out["computed"])


def test_a_program_without_the_block_fails_the_cell_at_once():
    """What the parent does with the new cell: ``hybrid_layer_pattern``, the
    first of ``model_keys``, is a name its ``ModelConfig`` lacks, and the run
    ends with exit code 2 before any device work (here: a configuration that
    lists one more)."""
    sys.path.insert(0, ROOT)
    from benchmarks import common

    config = published_config()
    assert config["model_keys"][0] == "hybrid_layer_pattern"
    m = common.model_section(config)
    assert m["model_type"] == "mimo_v2" and m["sliding_window"] == 128
    assert m["head_dim"] == 192 and m["n_routed_experts"] == 8
    assert m["rms_norm_eps"] == config["layernorm_epsilon"] == 1e-5
    assert common.load_reference(config).__file__.endswith("mimo_v2.py")
    config["model_keys"] = ["sink_mystery"] + config["model_keys"]
    config["sink_mystery"] = 1
    with pytest.raises(SystemExit) as e:
        common.model_section(config)
    assert e.value.code == 2


def test_the_configuration_keeps_every_published_number():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiMo-V2.5")
    config = published_config()
    assert config["source"] == row["source_url"]
    assert config["reduced"] == [
        "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
        "n_routed_experts", "vocab_size", "ep_size"]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert config["reduced_from"]["n_routed_experts"] == row["config"][
        "n_routed_experts"] == config["n_routed_experts"] * config["ep_size"]
    first = config["first_layer"]
    for key in ("hybrid_layer_pattern", "moe_layer_freq"):
        pub = row["config"][key]
        assert config[key] == [pub[0]] + pub[first:first + 12], key
    assert row["config"]["vocab_size"] == 8 * config["vocab_size"]
    assert any("multi-token prediction" in d and "towers" in d
               for d in config["departures"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [c for c in manifest["configs"] if c["name"] == NAME]
    assert entry["reduced"] == config["reduced"]
    cell, = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] \
        == "mixedctx-decode-closed-16k"
    assert len(manifest["workloads"]) >= 10  # PR 46 added the eleventh
