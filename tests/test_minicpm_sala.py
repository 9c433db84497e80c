"""The MiniCPM-SALA block (models/minicpm_sala.py) on the serving path, at
toy size in float32 on the CPU with seeded weights (window 8 / stride 4 /
block 16 / top 4 of ``dense_len`` 64: the published 32 / 16 / 64 scaled down
together), against the plain reference
(benchmarks/reference/minicpm_sala.py): the engine's programs through a
cache of four kinds of leaf, the compressed keys across chunk and decode
seams, the block selection against the reference's (ties, forced blocks),
what a state with no token axis asks of the programs, the moved
``select_keys`` and scan against what Granite and DeepSeek ran before, the
counters, and what ``Config.validate`` refuses."""

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
from jax import lax

from picotron_tpu.inference import InferenceEngine
from picotron_tpu.models import minicpm_sala as sala
from picotron_tpu.ops.select import select_keys
from picotron_tpu.ops.ssm import ssm_scan, ssm_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "minicpm-sala-l12.serve-longctx-decode"
F32 = jnp.float32

SPARSE = block_toys.SPARSE
TOY = block_toys.TOYS["minicpm_sala"]


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT,
                                                                     path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("benchmarks/reference/minicpm_sala.py", "reference_minicpm_sala")


make_config = partial(block_toys.make_config, "minicpm_sala", seq_length=256)


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, **{"slots": 2, "max_seq_len": 256,
                                     "prefill_chunk": 32, **kw})
    params = jax.jit(lambda k: sala.init_params(k, cfg.model))(
        jax.random.PRNGKey(7))
    return cfg, engine, engine.shard_params(params)


@pytest.fixture(autouse=True)
def short_scan_chunks(monkeypatch):
    """A prefill chunk of 32 rows in scan chunks of 8, as the cell's 512 go
    in chunks of 256; put back after every test."""
    monkeypatch.setattr(sala, "SCAN_CHUNK", 8)


@pytest.fixture(scope="module")
def toy():
    return make_engine()


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


def decode_block(engine, params, cache, toks, budget):
    keys = np.stack([np.asarray(jax.random.PRNGKey(i))
                     for i in range(engine.decode_block_len)])
    n = engine.slots
    return engine.decode_block(
        params, cache, np.asarray(toks, np.int32), keys,
        -np.ones(n, np.int32), np.asarray(budget, np.int32),
        np.zeros(n, np.float32), np.zeros(n, np.int32),
        np.ones(n, np.float32))


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
LONG = [int(t) for t in RNG.integers(1, 256, 240)]
OTHER = [int(t) for t in RNG.integers(1, 256, 100)]


# ---- (a) the programs against the reference --------------------------------


@pytest.mark.parametrize("n_prompt,steps", [
    (20, 12),    # one shot, every row under the dense rule
    (31, 6),     # one shot, its bucket's pad row beside it
    (60, 10),    # chunks; decode crosses dense_len (64) and two strides
    (63, 3),     # the last dense row is a prompt's last
    (96, 12),    # three whole chunks, then a decode block's worth and more
    (150, 9),    # five chunks across dense_len, a short last one
    (230, 12),   # 15 blocks of which 4 are kept
])
def test_prefill_and_decode_match_the_reference(toy, n_prompt, steps):
    _, engine, params = toy
    seq, got, _ = program_logits(engine, params, LONG[:n_prompt], steps)
    want = reference_rows(params, seq, n_prompt)
    assert worst_rel_err(got, want) < 1e-4


def test_the_whole_forward_matches_the_reference_at_every_position(toy):
    """One pass with no cache (the one-shot program's body) against the
    reference at every row: rows on both sides of ``dense_len``."""
    cfg, engine, params = toy
    S = 200
    tokens = jnp.asarray([LONG[:S]], jnp.int32)

    cos, sin = sala.serving_rope_tables(cfg.model, S, F32)

    def forward(params, tokens):
        h = engine._embed(params, tokens)
        live = jnp.ones(tokens.shape, bool)
        h, _, _ = engine._prefill_groups(params, h, cos, sin, live)
        return sala.head_logits(params, h, cfg)

    from jax.sharding import PartitionSpec as P
    from picotron_tpu.utils import shard_map
    forward = jax.jit(shard_map(forward, engine.topo.mesh,
                                in_specs=(engine._pspecs, P()),
                                out_specs=P()))
    got = np.asarray(forward(params, tokens), np.float32)[0]
    want = ref.forward_logits(params, np.asarray(tokens), dict(TOY),
                              jax.devices()[0])[0]
    assert np.max(np.abs(got - want)) / np.max(np.abs(want)) < 1e-4


@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_a_chunk_boundary_changes_nothing(chunk):
    """The same prompt in chunks of 16, 32 and 64: compressed windows and
    the lightning state cross other seams, the logits agree."""
    _, engine, params = make_engine(prefill_chunk=chunk)
    seq, got, _ = program_logits(engine, params, LONG[:150], steps=2)
    assert worst_rel_err(got, reference_rows(params, seq, 150)) < 1e-4


# ---- (b) the compressed keys ------------------------------------------------


@pytest.mark.parametrize("n_prompt,steps,how", [
    (100, 0, "a window across every chunk seam"),
    (46, 23, "a window across decode steps and a decode-block seam"),
    (27, 30, "one shot, then windows the decode steps end"),
])
def test_compressed_keys_are_the_means_of_the_cached_keys(toy, n_prompt,
                                                          steps, how):
    """Row ``r`` of ``kc`` is the mean of keys ``4 (r - 1) .. 4 (r - 1) + 8``
    of the same slot's ``k``, for every window the sequence has completed:
    whichever program wrote the keys, and wherever the window began."""
    _, engine, params = toy
    cache, last = admit(engine, params, engine.init_cache(),
                        LONG[:n_prompt], 0)
    n, tok = n_prompt, int(np.argmax(last))
    while n < n_prompt + steps:
        take = min(3, n_prompt + steps - n)  # blocks of three steps
        r = decode_block(engine, params, cache, [tok, 0], [take, 0])
        cache, n = r.cache, n + take
        tok = int(np.asarray(r.tokens)[0, take - 1])
    assert int(cache["lengths"][0]) == n
    k = np.asarray(cache["k"])[:, 0]   # [layers, kv heads, T, d]
    kc = np.asarray(cache["kc"])[:, 0]
    done = (n - 8) // 4 + 1
    assert done >= 5
    for c in range(done):
        want = k[:, :, 4 * c:4 * c + 8].mean(axis=2)
        np.testing.assert_allclose(kc[:, :, c + 1], want, rtol=1e-5,
                                   atol=1e-6, err_msg=f"window {c}: {how}")


def test_a_stale_compressed_key_across_a_seam_would_show(toy, monkeypatch):
    """The control of the test above: with the ``st`` keys before a chunk
    taken as zeros, the first window of every chunk is wrong and the logits
    leave the reference."""
    block = sala.compress_block
    monkeypatch.setattr(
        sala, "compress_block",
        lambda k, prev, st: block(k, jnp.zeros_like(prev), st))
    _, engine, params = make_engine(fresh=True)
    seq, got, _ = program_logits(engine, params, LONG[:230], steps=2)
    assert worst_rel_err(got, reference_rows(params, seq, 230)) > 1e-3


# ---- (c) the block selection ------------------------------------------------


def _selection_inputs(S, seed, tied=False):
    m = make_config().model
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((S, 4, 16)).astype(np.float32)
    k = rng.standard_normal((S, 2, 16)).astype(np.float32)
    if tied:
        # every key alike: every window's score ties with every other's
        k = np.broadcast_to(k[:1], k.shape).copy()
    kc = np.asarray(ref.compressed_keys(jnp.asarray(k), st=4))
    rows = np.zeros((1, 2, S // 4, 16), np.float32)  # row r: window r - 1
    rows[0, :, 1:1 + kc.shape[0]] = np.swapaxes(kc, 0, 1)[:, :S // 4 - 1]
    return m, q, kc, rows


@pytest.mark.parametrize("tied", [False, True], ids=["random", "all_tied"])
def test_kept_blocks_equal_the_references_exactly(tied):
    S = 240
    m, q, kc, rows = _selection_inputs(S, 11, tied)
    t = np.arange(S)
    got = np.asarray(sala.select_blocks(
        jnp.asarray(q[None]), jnp.asarray(rows), jnp.asarray(t[None],
                                                             jnp.int32),
        m))[0]
    want = np.asarray(ref.kept_blocks(
        jnp.asarray(q), jnp.asarray(t), jnp.asarray(kc), blocks=S // 16,
        **ref.sparse_sizes(dict(TOY))))
    np.testing.assert_array_equal(got, want)
    if tied:
        # ties go to the lower index: block 0 (forced), the two of the
        # window, and of the rest the first
        row = got[200, 0]
        assert list(np.flatnonzero(row)) == [0, 1, 11, 12]


def test_forced_blocks_are_always_kept_and_counted():
    S = 240
    m, q, _, rows = _selection_inputs(S, 5)
    t = np.arange(S)
    got = np.asarray(sala.select_blocks(
        jnp.asarray(q[None]), jnp.asarray(rows),
        jnp.asarray(t[None], jnp.int32), m))[0]  # [S, kv heads, blocks]
    for pos in range(S):
        cur = pos // 16
        kept = got[pos]
        assert not kept[:, cur + 1:].any()  # nothing past the query's own
        if pos < 64:  # the dense rule: every block up to its own
            assert kept[:, :cur + 1].all()
            continue
        assert kept[:, 0].all() and kept[:, cur - 1:cur + 1].all()
        # the forced three count among the four kept
        assert (kept.sum(axis=-1) == 4).all()


# ---- (d) what moved: select_keys and the scan, bit for bit ------------------


def _old_select_keys(scores, k: int):
    """``deepseek_v32.select_keys`` as DeepSeek's cell ran it before it
    moved to ``ops/select.py``."""
    T = scores.shape[-1]
    valid = scores > -jnp.inf
    if k >= T:
        return valid
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)
    nibble = jnp.arange(1, 16, dtype=jnp.uint32)

    def body(i, thr):
        shift = jnp.uint32(28) - 4 * i.astype(jnp.uint32)
        cands = thr[..., None] | (nibble << shift)
        n = jnp.sum(key[..., None, :] >= cands[..., None], axis=-1,
                    dtype=jnp.int32)
        return thr | (jnp.sum(n >= k, axis=-1).astype(jnp.uint32) << shift)

    thr = lax.fori_loop(0, 8, body, jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > thr[..., None]
    ties = key == thr[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    first = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room[..., None]
    return (above | (ties & first)) & valid


def _old_ssm_scan(xs, dt, A, Bm, Cm, S_in, chunk: int):
    """``granite_hybrid.ssm_scan`` as Granite's cell ran it before it moved
    to ``ops/ssm.py``."""
    HIGHEST = lax.Precision.HIGHEST
    B, S, nh, hd = xs.shape
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        xs, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                  (a.ndim - 2)) for a in (xs, dt, Bm, Cm))

    def chunks(a):
        return jnp.moveaxis(a.reshape(B, -1, Q, *a.shape[2:]), 1, 0)

    tri = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]

    def one(state, c):
        x_c, dt_c, B_c, C_c = c
        L = jnp.cumsum(jnp.moveaxis(dt_c * A, 2, 1), axis=-1)
        G = jnp.einsum("btn,bsn->bts", C_c, B_c, preferred_element_type=F32)
        decay = jnp.exp(jnp.where(tri, L[..., :, None] - L[..., None, :],
                                  -jnp.inf))
        xdt = x_c.astype(F32) * dt_c[..., None]
        y = jnp.einsum("bhts,bshp->bthp", G[:, None] * decay, xdt)
        C32, B32 = C_c.astype(F32), B_c.astype(F32)
        y = y + jnp.einsum("btn,bhpn->bthp", C32, state, precision=HIGHEST) \
            * jnp.moveaxis(jnp.exp(L), 1, 2)[..., None]
        to_end = jnp.moveaxis(jnp.exp(L[..., -1:] - L), 1, 2)
        state = jnp.exp(L[..., -1])[..., None, None] * state + jnp.einsum(
            "bshp,bsn->bhpn", xdt * to_end[..., None], B32,
            precision=HIGHEST)
        return state, y

    state, y = lax.scan(one, S_in, tuple(chunks(a)
                                         for a in (xs, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, -1, nh, hd)
    return y[:, :S], state


def _old_ssm_step(xs, dt, A, Bm, Cm, S_in):
    x32 = xs[:, 0].astype(F32) * dt[:, 0, :, None]
    state = jnp.exp(dt[:, 0] * A)[..., None, None] * S_in \
        + x32[..., None] * Bm[:, 0].astype(F32)[:, None, None, :]
    y = jnp.sum(state * Cm[:, 0].astype(F32)[:, None, None, :], axis=-1)
    return y[:, None], state


def _scan_inputs(S, per_head, dtype=F32, seed=0):
    rng = np.random.default_rng(seed)
    B, nh, hd, N = 2, 4, 8, 16
    bc = (B, S, nh, N) if per_head else (B, S, N)
    xs = jnp.asarray(rng.standard_normal((B, S, nh, hd)), dtype)
    dt = jnp.asarray(rng.uniform(0.01, 0.3, (B, S, nh)), F32)
    A = -jnp.asarray(rng.uniform(0.5, 4.0, nh), F32)
    Bm = jnp.asarray(rng.standard_normal(bc), dtype)
    Cm = jnp.asarray(rng.standard_normal(bc), dtype)
    S0 = jnp.asarray(rng.standard_normal((B, nh, hd, N)), F32)
    return xs, dt, A, Bm, Cm, S0


@pytest.mark.parametrize("S,chunk,dtype", [
    (37, 8, "float32"), (16, 8, "bfloat16"), (23, 256, "float32")])
def test_the_moved_scan_is_granites_bit_for_bit(S, chunk, dtype):
    args = _scan_inputs(S, per_head=False, dtype=jnp.dtype(dtype))
    for new, old in zip(jax.jit(ssm_scan, static_argnums=6)(*args, chunk),
                        jax.jit(_old_ssm_scan, static_argnums=6)(*args,
                                                                 chunk)):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))
    one = tuple(a[:, :1] if a.ndim > 1 and a.shape[1] == S else a
                for a in args)
    for new, old in zip(jax.jit(ssm_step)(*one), jax.jit(_old_ssm_step)(*one)):
        np.testing.assert_array_equal(np.asarray(new), np.asarray(old))


def test_the_moved_select_keys_is_deepseeks_bit_for_bit():
    from picotron_tpu.models import deepseek_v32 as dsv

    assert dsv.select_keys is select_keys
    rng = np.random.default_rng(2)
    s = rng.standard_normal((2, 5, 300)).astype(np.float32)
    s[0, 1, 40:90] = s[0, 1, 40]      # ties at the threshold
    s[1, :, 250:] = -np.inf           # keys past the query
    s[1, 3, :] = -np.inf              # a query that sees nothing
    s[0, 2, :7] = np.inf
    for k in (1, 40, 299, 300, 512):
        np.testing.assert_array_equal(
            np.asarray(jax.jit(select_keys, static_argnums=1)(s, k)),
            np.asarray(jax.jit(_old_select_keys, static_argnums=1)(s, k)))


@pytest.mark.parametrize("S,chunk", [(37, 8), (5, 8), (16, 8), (23, 256)])
def test_a_heads_own_b_and_c_scan_is_the_sequential_recurrence(S, chunk):
    """Lightning attention's form of the recurrence (``B``, ``C`` a head's
    own): the chunked scan against the one-row step against the recurrence
    written out."""
    xs, dt, A, Bm, Cm, S0 = _scan_inputs(S, per_head=True, seed=4)
    y, state = ssm_scan(xs, dt, A, Bm, Cm, S0, chunk)
    s, ys = S0, []
    for t in range(S):
        y_t, s = ssm_step(xs[:, t:t + 1], dt[:, t:t + 1], A, Bm[:, t:t + 1],
                          Cm[:, t:t + 1], s)
        ys.append(y_t)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jnp.concatenate(ys, 1)),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(s), rtol=2e-4,
                               atol=2e-5)
    # and the step is S = exp(dt A) S + dt x (x) B, y = S C, a head its own
    want = np.exp(np.asarray(dt[:, 0] * A))[..., None, None] * np.asarray(S0) \
        + np.einsum("bhp,bhn->bhpn",
                    np.asarray(xs[:, 0] * dt[:, 0, :, None]),
                    np.asarray(Bm[:, 0]))
    _, got = ssm_step(xs[:, :1], dt[:, :1], A, Bm[:, :1], Cm[:, :1], S0)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)


# ---- (e) a state with no token axis -----------------------------------------


def test_pad_rows_leave_the_state_as_at_length(toy):
    """44 tokens: a chunk and 12 rows + 20 pad rows; one shot in a bucket
    of 64 with 20 pad rows; the reference's recurrence: the same state."""
    _, engine, params = toy
    prompt = LONG[:44]
    chunked, _ = engine.prefill_chunked(params, engine.init_cache(), prompt,
                                        0)
    wide = make_engine(prefill_chunk=64)[1]
    kv, _ = wide.prefill(params, prompt)
    one = wide.insert(wide.init_cache(), kv, 0, 44)
    a, b = (np.asarray(c["state"])[:, 0] for c in (chunked, one))
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert np.abs(a).max() > 0
    # a 45th token, fed through a chunk of its own, moves it
    more, _ = engine.prefill_chunked(params, engine.init_cache(),
                                     prompt + [7], 0)
    assert np.abs(np.asarray(more["state"])[:, 0] - a).max() > 1e-3


@pytest.mark.parametrize("chunk", [32, 64])
def test_a_slot_used_twice_forgets_its_first_occupant(toy, chunk):
    _, engine, params = make_engine(prefill_chunk=chunk)
    _, _, cache = program_logits(engine, params, OTHER[:70], steps=3)
    cache = engine.release(cache, 0)
    seq, got, _ = program_logits(engine, params, LONG[:50], steps=3,
                                 cache=cache)
    assert worst_rel_err(got, reference_rows(params, seq, 50)) < 1e-4


def test_a_slot_that_is_not_live_does_not_advance_in_a_decode_block(toy):
    _, engine, params = toy
    cache, last0 = admit(engine, params, engine.init_cache(), LONG[:91], 0)
    cache, last1 = admit(engine, params, cache, OTHER[:30], 1)
    before = {n: np.asarray(cache[n][:, 1]) for n in ("state", "kc")}
    moved = np.asarray(cache["state"][:, 0])
    engine.take_stats()
    toks = [int(np.argmax(last0)), int(np.argmax(last1))]
    r = decode_block(engine, params, cache, toks, [5, 0])
    assert list(np.asarray(r.counts)) == [5, 0]
    assert list(np.asarray(r.cache["lengths"])) == [96, 30]
    for n in ("state", "kc"):  # slot 1 is parked and out of budget
        np.testing.assert_array_equal(np.asarray(r.cache[n][:, 1]),
                                      before[n])
    assert np.abs(np.asarray(r.cache["state"][:, 0]) - moved).max() > 0
    stats = dict(zip(sala.STAT_NAMES, engine.take_stats()))
    # 8 steps x 4 lightning layers ran; slot 0 advanced in 5 of the steps
    assert stats["lightning_layer_steps"] == 8 * 4
    assert stats["lightning_state_updates"] == 5 * 4
    # slot 0's five rows in three sparse layers, past dense_len: 2 kv heads
    # x 4 kept of the 6 blocks up to the query's own
    assert stats["sparse_rows"] == 5 * 3 and stats["dense_rows"] == 0
    assert stats["sparse_blocks_selected"] == 5 * 3 * 2 * 4
    assert stats["sparse_blocks_visible"] == 5 * 3 * 2 * 6
    # slot 1 decodes on from where it stood, as the reference has it
    seq = OTHER[:30] + [toks[1]]
    _, logits = decode(engine, params, r.cache, seq[-1], 1)
    want = reference_rows(params, seq, len(seq))
    assert worst_rel_err([logits], want) < 1e-4


@pytest.mark.parametrize("rounded", [False, True])
def test_the_state_of_a_bfloat16_model_is_float32_all_the_way(monkeypatch,
                                                              rounded):
    """The configuration states float32 for the lightning state, and the
    serving check's logits cannot tell a state kept in bfloat16 from it
    (the output norm and every activation beside it are rounded too). This
    can: after a chunked admission and decode steps of a bfloat16 model the
    slot's state is float32 and next to none of its entries are ones
    bfloat16 holds exactly; rounded anywhere on its way
    (``benchmarks/tests/control_sala.py``'s ``state_bf16``), all are."""
    if rounded:
        mixer = sala.lightning_mixer

        def rounding(*args, **kw):
            out, state = mixer(*args, **kw)
            return out, lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)

        monkeypatch.setattr(sala, "lightning_mixer", rounding)
    _, engine, params = make_engine({"dtype": "bfloat16"}, fresh=True)
    _, _, cache = program_logits(engine, params, LONG[:75])  # 3 chunks + 4
    state = cache["state"][:, 0]
    assert state.dtype == jnp.float32 and cache["kc"].dtype == jnp.bfloat16
    there = state != 0
    exact = state.astype(jnp.bfloat16).astype(jnp.float32) == state
    share = float(jnp.sum(exact & there) / jnp.sum(there))
    assert share == 1.0 if rounded else share < 0.01, share


def test_the_window_is_held_to_whole_chunks():
    with pytest.raises(ValueError, match="multiple of prefill_chunk"):
        InferenceEngine(make_config(), slots=2, max_seq_len=240,
                        prefill_chunk=32)
    with pytest.raises(ValueError, match="kernel_stride"):
        InferenceEngine(make_config(), slots=2, max_seq_len=252,
                        prefill_chunk=18)


# ---- (f) the tree, the groups, the cache ------------------------------------


def test_layer_groups_are_the_runs_of_mixer_types():
    m = make_config().model
    assert sala.runs(sala.kinds(m)) == [
        ("sparse", 0, 0, 1), ("lightning", 1, 0, 3), ("sparse", 4, 1, 2),
        ("lightning", 6, 3, 1)]
    assert [(n, c) for n, _, c in sala.layer_groups(m)] == [
        ("sparse_0", 1), ("lightning_1", 3), ("sparse_2", 2),
        ("lightning_3", 1)]
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala-l12.json")) as f:
        pub = json.load(f)
    full = pub["reduced_from"]["mixer_types"]
    assert len(full) == pub["reduced_from"]["num_hidden_layers"] == 32
    first = pub["first_layer"]
    assert pub["mixer_types"] == full[first:first + 12]
    # the published ratio, and the published adjacent pair
    assert (full.count("minicpm4"), full.count("lightning-attn")) == (8, 24)
    assert (pub["mixer_types"].count("minicpm4"),
            pub["mixer_types"].count("lightning-attn")) == (3, 9)
    assert pub["mixer_types"][7:9] == ["minicpm4", "minicpm4"]


def test_the_cache_has_four_kinds_of_leaf(toy):
    _, engine, _ = toy
    cache = engine.init_cache()
    shapes = {n: (tuple(a.shape), str(a.dtype)) for n, a in cache.items()}
    assert shapes == {
        "k": ((3, 2, 2, 256, 16), "float32"),
        "v": ((3, 2, 2, 256, 16), "float32"),
        "kc": ((3, 2, 2, 64, 16), "float32"),
        "state": ((4, 2, 4, 16, 16), "float32"),
        "lengths": ((2,), "int32")}
    assert sala.CARRIES_STATE


def test_seeded_draws_are_as_the_configuration_file_says():
    m = make_config().model
    p = jax.jit(lambda k: sala.init_params(k, m))(jax.random.PRNGKey(1))
    assert sala.num_params(m) == sum(v.size for v in jax.tree.leaves(p))
    g = p["lightning_1"]
    assert g["slope"].dtype == jnp.float32 and g["slope"].shape == (3, 4)
    # Lightning Attention's slopes at published layers 10, 11, 12 of 32
    for j, layer in enumerate((10, 11, 12)):
        want = 2.0 ** (-8.0 * np.arange(1, 5) / 4) * (1 - layer / 31 + 1e-5)
        np.testing.assert_allclose(np.asarray(g["slope"][j]), want,
                                   rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(p["lightning_3"]["slope"][0]),
        2.0 ** (-8.0 * np.arange(1, 5) / 4) * (1 - 15 / 31 + 1e-5),
        rtol=1e-6)
    bound = (1 / 64) ** 0.5
    assert np.abs(np.asarray(g["wo"])).max() <= bound
    top = np.abs(np.asarray(p["sparse_0"]["wo"])).max()
    gain = sala.INIT_GAIN["sparse"]["wo"]
    assert 0.9 * gain * bound < top <= gain * bound * 1.001
    # a unit-rms row of the embedding, once multiplied by scale_emb
    rows = np.sqrt(np.mean((np.asarray(p["embed"]) * 12.0) ** 2, axis=1))
    assert 0.7 < rows.mean() < 1.3
    assert sala.residual_scale(m) == pytest.approx(1.4 / 32 ** 0.5)


# ---- (g) what is refused, by name -------------------------------------------


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
    ({"mixer_types": ["minicpm4"] * 4}, "mixer_types"),
    ({"mixer_types": ["minicpm4", "window"] + ["lightning-attn"] * 5},
     "mixer_types"),
    ({"mixer_types": ["lightning-attn"] * 7}, "at least one"),
    ({"lightning_nkv": 2}, "lightning_nkv"),
    ({"lightning_head_dim": 0}, "lightning_head_dim"),
    ({"attn_use_rope": True}, "attn_use_rope"),
    ({"lightning_use_rope": False}, "lightning_use_rope"),
    ({"qk_norm": False}, "qk_norm"),
    ({"use_output_gate": False}, "use_output_gate"),
    ({"lightning_scale": "1"}, "lightning_scale"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"sparse_config": None}, "sparse_config"),
    ({"sparse_config": dict(SPARSE, kernel_size=12)}, "kernel_size"),
    ({"sparse_config": dict(SPARSE, topk=2)}, "forced blocks"),
    ({"first_layer": 30}, "total_layers"),
    ({"model_type": "minicpm4"}, "unknown model_type"),
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


# ---- (h) the counters, the program's name, the front end --------------------


def test_bfloat16_fails_the_float32_check(toy):
    """The program in the nearest precision below fails the 1e-3 the
    float32 engine is held to, read along the tokens the sound run chose."""
    _, engine, params = toy
    seq, got, _ = program_logits(engine, params, LONG[:100])
    want = reference_rows(params, seq, 100)
    assert worst_rel_err(got, want) < 1e-3
    _, low, _ = make_engine({"dtype": "bfloat16"})
    low_params = low.shard_params(jax.tree.map(
        lambda v: v if v.dtype == jnp.float32 and v.ndim == 2
        and v.shape[-1] == 4 else v.astype(jnp.bfloat16), params))
    cache, last = admit(low, low_params, low.init_cache(), LONG[:100])
    got_low = [last]
    for tok in seq[100:]:
        cache, logits = decode(low, low_params, cache, tok)
        got_low.append(logits)
    assert worst_rel_err(got_low, want) > 1e-3


def test_stats_leave_the_programs_a_row_a_layer(toy):
    _, engine, params = toy
    engine.take_stats()
    engine.prefill_chunked(params, engine.init_cache(), LONG[:100], 0)
    rows = sum(np.asarray(s) for s in engine._stats_pending)
    engine.take_stats()
    assert rows.shape == (7, len(sala.STAT_NAMES))
    names = dict(zip(sala.STAT_NAMES, rows.T))
    sparse = [1, 0, 0, 0, 1, 1, 0]
    # 100 live rows a layer: 64 under the dense rule, 36 under the sparse
    assert list(names["lightning_scan_tokens"]) == [
        0 if s else 100 for s in sparse]
    assert list(names["dense_rows"]) == [64 * s for s in sparse]
    assert list(names["sparse_rows"]) == [36 * s for s in sparse]
    # rows 64 .. 99: blocks up to the query's own 5 (16 rows), 6 (16), 7 (4)
    assert list(names["sparse_blocks_visible"]) == [
        2 * (16 * 5 + 16 * 6 + 4 * 7) * s for s in sparse]
    assert list(names["sparse_blocks_selected"]) == [
        2 * 36 * 4 * s for s in sparse]
    assert not names["lightning_layer_steps"].any()  # no decode step


def test_the_batcher_puts_the_counters_on_metrics():
    from picotron_tpu.inference import ContinuousBatcher, Request

    _, engine, params = make_engine(fresh=True)
    batcher = ContinuousBatcher(engine, params, seed=0)
    reqs = [Request(uid=f"r{i}", prompt=p, max_new_tokens=5)
            for i, p in enumerate((LONG[:90], OTHER[:9], OTHER[:40]))]
    out = batcher.run(reqs)
    assert all(len(out[r.uid].tokens) == 5 for r in reqs)
    text = engine.obs.registry.prometheus()
    got = {}
    for name in sala.STAT_NAMES:
        line, = [l for l in text.splitlines()
                 if l.startswith(f"picotron_{name}_total ")]
        got[name] = float(line.split()[1])
    assert got["lightning_scan_tokens"] == 4 * (90 + 9 + 40)
    # each request's five tokens: one from its prefill, four decode steps
    assert got["lightning_state_updates"] == 4 * 3 * 4
    assert got["lightning_layer_steps"] >= got["lightning_state_updates"] / 2
    # prefill rows and decode rows of three sparse layers
    assert got["sparse_rows"] + got["dense_rows"] == 3 * (90 + 9 + 40 + 12)
    assert got["sparse_rows"] == 3 * (90 - 64 + 4)
    assert 0 < got["sparse_blocks_selected"] < got["sparse_blocks_visible"]


def test_the_decode_program_keeps_the_name_the_readers_find(toy):
    """``engine.decode_bw_pct.sala`` finds the decode block on the trace's
    ``XLA Modules`` line by the name ``benchmarks/stats.py`` lists."""
    sys.path.insert(0, ROOT)
    from benchmarks import stats

    _, engine, params = toy
    keys = jnp.zeros((engine.decode_block_len, 2), jnp.uint32)
    n = engine.slots
    text = engine._program("decode_block").lower(
        params, engine.init_cache(), jnp.zeros((6, n), jnp.int32), keys
    ).as_text().split("\n", 1)[0]
    assert "module @jit__decode_block_impl " in text
    assert any(p in "jit__decode_block_impl" for p in stats.DECODE_PROGRAMS)


def test_the_front_end_admits_a_full_house_of_long_prompts():
    """Eight prompts that nearly fill eight slots (the cell's 8 x 57k on 8 x
    65,536, at toy size): the front end's token budget and queue take them
    all at once and shed none."""
    from picotron_tpu.tools import serve

    _, engine, params = make_engine(slots=8)
    front = serve.FrontEnd(engine, params, seed=0)
    assert front.token_budget == 8 * 256 and front.max_queue >= 8
    prompt_len, new = 224, 24  # 57,344 + 6,144 of 65,536, scaled by 256
    uids = [front.submit({"prompt": LONG[:prompt_len],
                          "max_new_tokens": new})[0]
            for _ in range(8)]
    assert len(set(uids)) == 8
    with pytest.raises(serve.AdmissionError):  # a ninth passes the budget
        front.submit({"prompt": LONG[:prompt_len], "max_new_tokens": new})


# ---- (i) the cell's rehearsal -----------------------------------------------


def test_rehearsal_of_the_cell_computes_its_readers():
    """Under six test workers a first token came later than the mix's
    rehearsal lead-in of 1 s and the 2 s window that followed held a prefill
    and no decode round (the driver's run before PR 39: the three counter
    readers found nothing to divide by). A lead-in and a window of 4 s each
    hold decode rounds whatever the first prefill waited for."""
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         "3000000001", "--seconds", "4", "--trace", "2", "--rehearse",
         "--set", "lead_in_seconds=4"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    assert out["rehearsal"] is True and out["metrics"] == {}
    assert out["device"]["platform"] == "cpu"
    # the device-trace readers need a chip
    assert {"serve_out_tokens_per_s", "setup_s", "infllm.selected_pct",
            "infllm.sparse_rows_pct",
            "lightning.state_updates_per_step"} <= set(out["computed"])


def test_a_program_without_the_block_fails_the_cell_at_once():
    """What the parent does with the new cell: the first ``model_keys``
    name ``ModelConfig`` lacks ends the run with exit code 2 before any
    device work (here: a configuration that lists one more)."""
    sys.path.insert(0, ROOT)
    from benchmarks import common

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala-l12.json")) as f:
        config = json.load(f)
    m = common.model_section(config)
    assert m["model_type"] == "minicpm_sala"
    assert m["sparse_config"]["topk"] == 64 and m["first_layer"] == 9
    assert common.load_reference(config).__file__.endswith("minicpm_sala.py")
    config["model_keys"] = config["model_keys"] + ["lightning_mystery"]
    config["lightning_mystery"] = 1
    with pytest.raises(SystemExit) as e:
        common.model_section(config)
    assert e.value.code == 2
