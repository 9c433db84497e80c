"""Speculative decoding (ISSUE-4): draft-verify pipeline on top of blocked
decode.

Acceptance surface:
- greedy speculative decode (prompt-lookup drafter, any draft quality) is
  BIT-IDENTICAL to the spec-off batcher streams on tp=1 and a tp=2 dryrun
  mesh, EOS mid-verify included;
- a drafter that guesses right turns dispatches-per-token into
  1/(spec_len+1): a scripted oracle drafter pins the dispatch count and a
  100% accept rate;
- the acceptance rule is distribution-preserving: greedy rows take the
  exact-match fast path (unit-pinned emitted prefixes), stochastic rows
  rejection-sample with residual resampling — a seeded statistical test
  pins the emitted-token frequencies against the non-speculative
  sampler's filtered softmax, at the pure-function level AND through the
  real verify dispatch;
- rollback is the length pointer: a rejected draft's optimistically
  written K/V rows leave ``attend`` output bit-identical to never having
  written them (bf16 and int8 caches);
- the n-gram drafter proposes cycle continuations from the slot's own
  history (longest suffix first) and always returns exactly n tokens.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_config, with_heads
from picotron_tpu.config import Config, SpecControllerConfig
from picotron_tpu.inference import (
    ContinuousBatcher,
    InferenceEngine,
    LearnedDrafter,
    NgramDrafter,
    Request,
    SpecController,
    init_draft_head,
    kv_cache,
    sampling,
)
from picotron_tpu.inference.speculative import Drafter
from picotron_tpu.models import llama
from picotron_tpu.obs.metrics import MetricsRegistry

MAX_LEN = 96


def _engine(tiny_model_kwargs, tp=1, slots=2, **kw):
    cfg = make_config(tiny_model_kwargs, tp=tp, seq=MAX_LEN)
    return cfg, InferenceEngine(cfg, slots=slots, max_seq_len=MAX_LEN, **kw)


def _params(cfg, engine, seed=0):
    p = jax.jit(lambda k: llama.init_params(k, cfg.model))(
        jax.random.PRNGKey(seed))
    return engine.shard_params(p)


class ScriptedDrafter(Drafter):
    """Oracle drafter for tests: proposes the known future of one scripted
    sequence (prompt + expected tokens) by matching the history length."""

    def __init__(self, script):
        self.script = list(script)

    def propose(self, history, n):
        start = len(np.asarray(history).reshape(-1))
        out = np.zeros(n, np.int32)
        tail = self.script[start: start + n]
        out[: len(tail)] = tail
        return out


# --------------------------------------------------------------------------- #
# greedy speculation == spec-off, bit for bit
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("tp,spec_len,heads", [
    (1, 2, "d8"), (1, 4, "d8"), (2, 3, "d8"), (1, 4, "d64"), (2, 3, "d64"),
    (1, 3, "d32")])
def test_greedy_spec_matches_spec_off(tiny_model_kwargs, tp, spec_len, heads):
    """Mixed-length greedy requests through the speculative batcher (the
    real NgramDrafter — accepts and rejections both occur) must produce
    the spec-off engine's streams token for token."""
    tiny_model_kwargs = with_heads(tiny_model_kwargs, heads)
    cfg, eng_off = _engine(tiny_model_kwargs, tp=tp)
    _, eng_on = _engine(tiny_model_kwargs, tp=tp, spec_len=spec_len)
    params = _params(cfg, eng_off)
    reqs = [Request("a", [1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=17),
            Request("b", [9, 8, 7], max_new_tokens=6)]
    want = ContinuousBatcher(eng_off, params).run(reqs)
    got = ContinuousBatcher(eng_on, params).run(reqs)
    for r in reqs:
        assert got[r.uid].tokens == want[r.uid].tokens, (r.uid, tp, spec_len)
        assert got[r.uid].finish_reason == "length"


def test_greedy_spec_eos_mid_verify(tiny_model_kwargs):
    """A stream whose EOS lands mid-verify (inside an accepted draft run
    or at the fresh token) must end AT the EOS — identical to spec-off —
    and the queued request behind it still completes."""
    cfg, eng_off = _engine(tiny_model_kwargs, slots=1)
    _, eng_on = _engine(tiny_model_kwargs, slots=1, spec_len=4)
    params = _params(cfg, eng_off)
    prompt = [5, 6, 7, 8]
    free = ContinuousBatcher(eng_off, params).run(
        [Request("f", prompt, max_new_tokens=12)])["f"]
    eos = free.tokens[5]
    assert eos not in free.tokens[:5], "pick a different seed/prompt"
    res = ContinuousBatcher(eng_on, params).run([
        Request("x", prompt, max_new_tokens=12, eos_id=eos),
        Request("y", [3, 1, 4], max_new_tokens=5),
    ])
    assert res["x"].finish_reason == "eos"
    assert res["x"].tokens == free.tokens[:6]
    assert res["y"].finish_reason == "length"
    assert len(res["y"].tokens) == 5


def test_scripted_drafter_dispatch_savings(tiny_model_kwargs):
    """An oracle drafter (knows the greedy future) must drive acceptance
    to 100% and the decode dispatch count to ceil((n-1)/(spec_len+1)) —
    the one-pass-per-accepted-run win speculation exists for."""
    cfg, eng_off = _engine(tiny_model_kwargs)
    _, eng_on = _engine(tiny_model_kwargs, spec_len=3)
    params = _params(cfg, eng_off)
    prompt = [1, 2, 3, 4, 5]
    n_new = 13
    want = ContinuousBatcher(eng_off, params).run(
        [Request("r", prompt, max_new_tokens=n_new)])["r"].tokens
    drafter = ScriptedDrafter(prompt + want)
    b = ContinuousBatcher(eng_on, params, drafter=drafter)
    got = b.run([Request("r", prompt, max_new_tokens=n_new)])["r"].tokens
    assert got == want
    assert b.accept_rate == 1.0
    # token 1 comes from the prefill sample; each verify emits spec_len+1
    assert b.decode_dispatches == math.ceil((n_new - 1) / 4)
    assert b.decode_dispatches < n_new - 1  # strictly beats per-token


def test_spec_respects_budget_and_window(tiny_model_kwargs):
    """Budgets that are not multiples of spec_len+1 (and a prompt close to
    the window) stop at exactly max_new_tokens — the device budget clip on
    the variable-length emit."""
    cfg, eng = _engine(tiny_model_kwargs, slots=2, spec_len=4)
    params = _params(cfg, eng)
    reqs = [Request("a", [1, 2, 3], max_new_tokens=7),
            Request("b", list(range(1, 90)), max_new_tokens=64)]
    res = ContinuousBatcher(eng, params).run(reqs)
    assert len(res["a"].tokens) == 7 and res["a"].finish_reason == "length"
    # 89 prompt tokens under MAX_LEN 96 leave exactly 7
    assert len(res["b"].tokens) == 7 and res["b"].finish_reason == "length"


# --------------------------------------------------------------------------- #
# acceptance rule: greedy fast path + distribution preservation
# --------------------------------------------------------------------------- #


def _logits_for_chain(chain, V, boost=8.0):
    """[S, V] logits whose argmax at position i is chain[i], with enough
    margin that the argmax is unambiguous."""
    rng = np.random.default_rng(0)
    out = rng.normal(size=(len(chain), V)).astype(np.float32)
    out[np.arange(len(chain)), chain] += boost
    return out


def test_accept_greedy_prefix():
    """Greedy rows accept exactly the matching draft prefix and emit the
    argmax correction (or the bonus token when everything matched)."""
    V = 11
    chain = [3, 7, 1, 4, 9]  # argmax at the 5 verify positions
    logits = jnp.asarray(_logits_for_chain(chain, V)[None])  # [1, 5, V]
    zero, one = jnp.zeros(1), jnp.ones(1)
    for n_match in range(5):
        draft = list(chain[:4])
        if n_match < 4:
            draft[n_match] = (draft[n_match] + 1) % V  # first mismatch
        emitted, counts = sampling.speculative_accept(
            logits, jnp.asarray([draft], jnp.int32), jax.random.PRNGKey(0),
            zero, jnp.zeros(1, jnp.int32), one)
        want = chain[: n_match + 1]  # accepted prefix == greedy chain
        assert int(counts[0]) == n_match + 1
        assert list(np.asarray(emitted)[0, : n_match + 1]) == want
        assert np.all(np.asarray(emitted)[0, n_match + 1:] == 0)


def test_accept_distribution_matches_sampler():
    """Seeded statistical test of the rejection/residual rule: over many
    keys, the FIRST emitted token's frequencies must converge to the
    non-speculative sampler's distribution (filtered softmax) — whether
    the draft token is likely or unlikely — and the draft must accept at
    ~its target probability. Also exercised with top-k filtering."""
    rng = np.random.default_rng(2)
    V = 8
    logits = jnp.asarray(rng.normal(size=(1, 2, V)).astype(np.float32))
    n = 4000
    keys = jax.random.split(jax.random.PRNGKey(0), n)
    one = jnp.ones(1)

    probs0 = np.asarray(jax.nn.softmax(logits[0, 0]))
    for draft_tok in (int(np.argmax(probs0)), int(np.argmin(probs0))):
        for top_k in (0, 3):
            draft = jnp.asarray([[draft_tok]], jnp.int32)
            ks = jnp.full(1, top_k, jnp.int32)

            def first_tok(key):
                emitted, _ = sampling.speculative_accept(
                    logits, draft, key, one, ks, one)
                return emitted[0, 0]

            toks = np.asarray(jax.vmap(first_tok)(keys))
            freq = np.bincount(toks, minlength=V) / n
            want = np.asarray(sampling.filtered_probs(
                logits[0, :1], one, ks, one))[0]
            np.testing.assert_allclose(freq, want, atol=0.04,
                                       err_msg=f"d={draft_tok} k={top_k}")
            # acceptance fires at the draft token's target probability
            def count(key):
                _, c = sampling.speculative_accept(
                    logits, draft, key, one, ks, one)
                return c[0]

            acc = np.mean(np.asarray(jax.vmap(count)(keys)) == 2)
            np.testing.assert_allclose(acc, want[draft_tok], atol=0.04)


def test_accept_second_position_distribution():
    """Given an accepted draft, the NEXT emitted token draws from the
    bonus position's own filtered softmax — the chain rule that makes the
    whole emitted run distributionally exact."""
    rng = np.random.default_rng(3)
    V = 8
    logits_np = rng.normal(size=(1, 2, V)).astype(np.float32)
    probs0 = np.asarray(jax.nn.softmax(jnp.asarray(logits_np[0, 0])))
    draft_tok = int(np.argmax(probs0))  # likely -> plenty of accepts
    logits = jnp.asarray(logits_np)
    draft = jnp.asarray([[draft_tok]], jnp.int32)
    one, zk = jnp.ones(1), jnp.zeros(1, jnp.int32)
    n = 4000
    keys = jax.random.split(jax.random.PRNGKey(1), n)

    def run(key):
        emitted, counts = sampling.speculative_accept(
            logits, draft, key, one, zk, one)
        return emitted[0, 1], counts[0]

    second, counts = jax.vmap(run)(keys)
    second, counts = np.asarray(second), np.asarray(counts)
    sel = counts == 2  # draft accepted: position 1 is the bonus draw
    assert sel.mean() > 0.25
    freq = np.bincount(second[sel], minlength=V) / sel.sum()
    want = np.asarray(jax.nn.softmax(logits[0, 1]))
    np.testing.assert_allclose(freq, want, atol=0.05)


def test_spec_sampled_e2e_distribution(tiny_model_kwargs):
    """The real verify dispatch preserves the sampler's distribution:
    park a prompt, feed a fixed last token + drafts, and over many keys
    the first emitted token's frequencies must match the filtered softmax
    of the full-forward oracle logits at that position (top-k 4
    concentrates the support so a few hundred draws resolve it)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    from picotron_tpu.utils import shard_map as shard_map_compat

    cfg, engine = _engine(tiny_model_kwargs, slots=1, spec_len=2)
    params = _params(cfg, engine)
    prompt = [7, 3, 5, 2, 7, 3]
    t0, top_k, temp = 9, 4, 1.0

    fwd = jax.jit(shard_map_compat(
        lambda p, t: llama.forward_logits(p, t, cfg), engine.topo.mesh,
        in_specs=(llama.param_pspecs(cfg.model), P()), out_specs=P()))
    oracle = np.asarray(fwd(params, jnp.asarray(
        np.asarray(prompt + [t0], np.int32)[None])))[0, -1]
    want = np.asarray(sampling.filtered_probs(
        jnp.asarray(oracle[None]), jnp.full(1, temp),
        jnp.full(1, top_k, jnp.int32), jnp.ones(1)))[0]
    draft_tok = int(np.argmax(want))  # exercises accept AND reject paths

    kv, _ = engine.prefill(params, prompt)
    cache0 = engine.insert(engine.init_cache(), kv, 0, len(prompt))
    cache0 = jax.tree.map(np.asarray, cache0)  # host copy: verify donates
    tokens = np.asarray([[t0, draft_tok, draft_tok]], np.int32)
    args = (np.full(1, -1, np.int32), np.full(1, 50, np.int32),
            np.full(1, temp, np.float32), np.full(1, top_k, np.int32),
            np.ones(1, np.float32))
    n = 400
    first = np.zeros(n, np.int32)
    for i in range(n):
        cache = jax.tree.map(jnp.asarray, cache0)
        r = engine.verify(
            params, cache, tokens, jax.random.PRNGKey(i), *args)
        emitted, counts = r.tokens, r.counts
        assert int(np.asarray(counts)[0]) >= 1
        first[i] = np.asarray(emitted)[0, 0]
    freq = np.bincount(first, minlength=cfg.model.vocab_size) / n
    kept = np.flatnonzero(want)
    assert set(np.flatnonzero(freq)) <= set(kept)
    np.testing.assert_allclose(freq[kept], want[kept], atol=0.09)


# --------------------------------------------------------------------------- #
# rollback: the length pointer IS the rewind
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("D", [8, 64])
@pytest.mark.parametrize("quantized", [False, True])
def test_rejected_draft_rows_invisible_to_attend(quantized, D):
    """Optimistically written draft rows beyond the post-acceptance length
    must leave ``attend`` output BIT-IDENTICAL to never having written
    them — for bf16 and int8 (scales included) caches, a head a row
    (heads of 8) and two a row (heads of 64). This is the whole rollback
    mechanism: rewinding is one length-pointer write."""
    rng = np.random.default_rng(0)
    B, T, H = 2, 16, 4
    dt = jnp.bfloat16
    pack = kv_cache.pack_factor(D, H)

    def block():
        base = {  # stacked leaves of a one-layer cache
            "k": jnp.asarray(rng.normal(size=(1, B, T, H, D)), dt),
            "v": jnp.asarray(rng.normal(size=(1, B, T, H, D)), dt),
        }
        if quantized:
            qk, ks = kv_cache.quantize_kv(base["k"])
            qv, vs = kv_cache.quantize_kv(base["v"])
            base = {"k": qk, "v": qv, "k_scale": ks, "v_scale": vs}
        return {n: kv_cache.pack_heads(a, pack) if n in "kv" else a
                for n, a in base.items()}

    base = block()
    pos = jnp.asarray([6, 3], jnp.int32)  # per-slot write offsets
    S = 4  # 1 fed token + 3 drafts
    k_new = jnp.asarray(rng.normal(size=(B, S, H, D)), dt)
    v_new = jnp.asarray(rng.normal(size=(B, S, H, D)), dt)
    # speculative write: all S rows land; suppose 0 drafts accepted, so the
    # post-acceptance lengths advance past the fed token only
    spec = kv_cache.cache_write(base, k_new, v_new, pos, 0)
    clean = kv_cache.cache_write(base, k_new[:, :1], v_new[:, :1], pos, 0)
    lengths = pos + 1

    q = jnp.asarray(rng.normal(size=(B, 1, H, D)), dt)
    out_spec = kv_cache.attend(q, spec, lengths, 0.3, 0)
    out_clean = kv_cache.attend(q, clean, lengths, 0.3, 0)
    np.testing.assert_array_equal(np.asarray(out_spec, np.float32),
                                  np.asarray(out_clean, np.float32))
    # and the next decode step's write simply overwrites a stale row
    k2 = jnp.asarray(rng.normal(size=(B, 1, H, D)), dt)
    v2 = jnp.asarray(rng.normal(size=(B, 1, H, D)), dt)
    again_spec = kv_cache.cache_write(spec, k2, v2, lengths, 0)
    again_clean = kv_cache.cache_write(clean, k2, v2, lengths, 0)
    out2s = kv_cache.attend(q, again_spec, lengths + 1, 0.3, 0)
    out2c = kv_cache.attend(q, again_clean, lengths + 1, 0.3, 0)
    np.testing.assert_array_equal(np.asarray(out2s, np.float32),
                                  np.asarray(out2c, np.float32))


def test_batched_write_drops_out_of_window_rows():
    """A speculative write window crossing the cache edge drops the
    out-of-range rows instead of clamping them onto earlier positions
    (the chunked-prefill bug class, pinned for the batched write)."""
    B, T, H, D = 2, 8, 2, 4
    base = {"k": jnp.zeros((1, B, T, H, D)), "v": jnp.zeros((1, B, T, H, D))}
    k_new = jnp.ones((B, 3, H, D))
    out = kv_cache.cache_write(base, k_new, k_new,
                               jnp.asarray([6, 2], jnp.int32), 0)
    got = np.asarray(out["k"][0, :, :, 0, 0])
    want = np.zeros((B, T))
    want[0, 6:8] = 1  # row at pos 8 dropped
    want[1, 2:5] = 1
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------------- #
# the n-gram drafter
# --------------------------------------------------------------------------- #


def test_ngram_drafter_cycle_continuation():
    d = NgramDrafter(3)
    hist = [1, 2, 3, 1, 2, 3, 1, 2]
    # suffix [3, 1, 2] matched at position 2 -> continuation cycles 3,1,2
    np.testing.assert_array_equal(d.propose(np.asarray(hist), 4),
                                  [3, 1, 2, 3])
    # proposals always have exactly n tokens
    assert d.propose(np.asarray(hist), 7).shape == (7,)


def test_ngram_drafter_longest_suffix_wins():
    # 1-gram match for 9 exists at position 0 (-> 5), but the 2-gram
    # suffix [2, 9] matches at 2 (-> 7): the longer context must win
    d = NgramDrafter(3)
    hist = [9, 5, 2, 9, 7, 2, 9]
    assert d.propose(np.asarray(hist), 1)[0] == 7


def test_ngram_drafter_fallback_repeats_last():
    d = NgramDrafter(3)
    np.testing.assert_array_equal(
        d.propose(np.asarray([4, 5, 6]), 3), [6, 6, 6])
    np.testing.assert_array_equal(d.propose(np.asarray([2]), 2), [2, 2])
    np.testing.assert_array_equal(d.propose(np.asarray([], np.int32), 2),
                                  [0, 0])


# --------------------------------------------------------------------------- #
# config / engine validation
# --------------------------------------------------------------------------- #


def test_spec_config_validation(tiny_model_kwargs):
    with pytest.raises(ValueError, match="spec_len"):
        Config.from_dict({"inference": {"spec_len": -1}})
    with pytest.raises(ValueError, match="spec_ngram"):
        Config.from_dict({"inference": {"spec_ngram": 0}})
    cfg = make_config(tiny_model_kwargs, seq=MAX_LEN)
    eng = InferenceEngine(cfg, max_seq_len=MAX_LEN)  # spec off by default
    assert eng.spec_len == 0
    with pytest.raises(ValueError, match="spec_len"):
        eng.verify(None, None, np.zeros((2, 3), np.int32), None,
                   None, None, None, None, None)
    # config knob flows through; keyword override wins
    cfg.inference.spec_len = 3
    assert InferenceEngine(cfg, max_seq_len=MAX_LEN).spec_len == 3
    assert InferenceEngine(cfg, max_seq_len=MAX_LEN,
                           spec_len=0).spec_len == 0


def test_controller_and_drafter_config_validation():
    with pytest.raises(ValueError, match="drafter"):
        Config.from_dict({"inference": {"drafter": "oracle"}})
    with pytest.raises(ValueError, match="spec_history_window"):
        Config.from_dict({"inference": {"spec_history_window": -1}})
    with pytest.raises(ValueError, match="spec_len > 0"):
        Config.from_dict(
            {"inference": {"spec_controller": {"enabled": True}}})
    with pytest.raises(ValueError, match="low"):
        Config.from_dict({"inference": {
            "spec_len": 4,
            "spec_controller": {"low": 0.9, "target": 0.5}}})
    with pytest.raises(ValueError, match="hysteresis"):
        Config.from_dict({"inference": {
            "spec_len": 4, "spec_controller": {"hysteresis": 0}}})
    # the nested block round-trips through to_dict/from_dict (the engine's
    # inference_config() path)
    cfg = Config.from_dict({
        "dataset": {"name": "synthetic"},
        "inference": {"spec_len": 4, "drafter": "learned",
                      "spec_controller": {"enabled": True, "window": 8}}})
    cfg2 = Config.from_dict(cfg.to_dict())
    assert cfg2.inference.spec_controller.window == 8
    assert cfg2.inference.drafter == "learned"


# --------------------------------------------------------------------------- #
# incremental n-gram index == full rebuild
# --------------------------------------------------------------------------- #


def test_ngram_incremental_matches_full_rebuild():
    """The append-only per-request index (ctx path) must answer every
    lookup exactly like the stateless full suffix scan, across growing
    histories — windowed and unbounded."""
    rng = np.random.default_rng(7)
    for window in (0, 12):
        inc = NgramDrafter(3, window=window)
        ref = NgramDrafter(3, window=window)
        inc.begin("r")
        hist = list(rng.integers(0, 6, 5))
        for round_ in range(40):
            h = np.asarray(hist, np.int32)
            got = inc.propose(h, 4, ctx="r")
            want = ref.propose(h, 4)  # stateless: full rebuild each call
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"w={window} r={round_}")
            # append-only growth, mixing repeats (matches) and fresh noise
            if round_ % 3 == 0:
                hist.extend(hist[-3:])
            hist.append(int(rng.integers(0, 6)))
        inc.forget("r")
        assert "r" not in inc._idx


def test_ngram_window_caps_match_scan():
    """A match whose continuation lives beyond the window must be ignored
    (falls back to shorter grams / last-token repeat)."""
    hist = np.asarray([7, 8, 9, 1, 1, 1, 1, 1, 1, 1, 7, 8], np.int32)
    # unbounded: suffix [7, 8] matches at position 0 -> proposes 9
    assert NgramDrafter(2).propose(hist, 1)[0] == 9
    # window 4: that match is out of reach; 1-gram 8 has no earlier
    # occurrence in the window either -> last-token fallback (8)
    assert NgramDrafter(2, window=4).propose(hist, 1)[0] == 8
    # the incremental path applies the same cap
    d = NgramDrafter(2, window=4)
    assert d.propose(hist, 1, ctx="x")[0] == 8


def test_ngram_stale_ctx_rebuilds_on_shrunk_history():
    """A slot recycled without begin() (history shrinks) must not answer
    from the dead request's index."""
    d = NgramDrafter(3)
    long_h = np.asarray([1, 2, 3, 4, 5, 1, 2, 3, 4], np.int32)
    d.propose(long_h, 2, ctx="s")
    short_h = np.asarray([9, 8], np.int32)
    np.testing.assert_array_equal(
        d.propose(short_h, 2, ctx="s"),
        NgramDrafter(3).propose(short_h, 2))


# --------------------------------------------------------------------------- #
# ragged verify: per-slot draft lengths in ONE dispatch
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("tp,impl,layout,quant,temp,heads", [
    (1, "dense", "contiguous", False, 0.0, "d8"),
    (1, "dense", "contiguous", True, 0.0, "d8"),
    (1, "dense", "contiguous", False, 1.0, "d8"),
    (1, "flash", "contiguous", False, 0.0, "d8"),
    (1, "dense", "paged", False, 0.0, "d8"),
    (1, "flash", "paged", True, 0.0, "d8"),
    (2, "dense", "contiguous", False, 0.0, "d8"),
    (2, "dense", "paged", True, 0.0, "d8"),
    # two heads of 64 a row of the contiguous cache (the pool is not packed)
    (1, "dense", "contiguous", False, 0.0, "d64"),
    (1, "dense", "contiguous", True, 0.0, "d64"),
    (2, "dense", "contiguous", False, 1.0, "d64"),
    (1, "flash", "contiguous", False, 0.0, "d64"),
    (1, "dense", "paged", False, 0.0, "d64"),
])
def test_ragged_verify_matches_per_slot_sequential(tiny_model_kwargs, tp,
                                                   impl, layout, quant,
                                                   temp, heads):
    """One RAGGED verify dispatch (per-slot draft_len) must emit, count,
    accept, and advance lengths exactly as per-slot SEQUENTIAL solo
    verifies (each slot alone with its own draft length) — across tp,
    attend kernels, KV layouts, and int8 storage. Row b's acceptance
    depends only on row b's logits and the shared key, so the group
    dispatch is the sum of its solo parts."""
    slots = 3
    cfg, engine = _engine(
        with_heads(tiny_model_kwargs, heads), tp=tp, slots=slots, spec_len=4,
        attend_impl=impl, kv_layout=layout,
        cache_dtype="int8" if quant else None)
    params = _params(cfg, engine)
    prompts = [[1, 2, 3, 1, 2, 3], [9, 8, 7, 6], [4, 4, 5]]
    draft_len = np.asarray([3, 1, 0], np.int32)
    rng = np.random.default_rng(0)
    drafts = rng.integers(1, cfg.model.vocab_size,
                          (slots, engine.spec_len)).astype(np.int32)
    key = jax.random.PRNGKey(5)
    eos = np.full(slots, -1, np.int32)
    temps = np.full(slots, temp, np.float32)
    tk = np.full(slots, 4 if temp > 0 else 0, np.int32)
    tp_ = np.ones(slots, np.float32)

    def one_run(budget):
        """Fresh cache + parked prompts, one verify dispatch."""
        cache = engine.init_cache()
        for s, p in enumerate(prompts):
            if layout == "paged":
                out = engine.prefill_paged(params, cache, p, s)
                cache = out[0]
            else:
                kv, _ = engine.prefill(params, p)
                cache = engine.insert(cache, kv, s, len(p))
        tokens = np.concatenate(
            [np.asarray([[p[-1]] for p in prompts], np.int32), drafts],
            axis=1)
        r = engine.verify(
            params, cache, tokens, key, eos, budget, temps, tk, tp_,
            draft_len=draft_len)
        cache, emitted = r.cache, r.tokens
        counts, accepted = r.counts, r.accepted
        return (np.asarray(emitted), np.asarray(counts),
                np.asarray(accepted), np.asarray(cache["lengths"]))

    full_budget = np.asarray([8, 2, 8], np.int32)  # slot 1: budget clip
    g_em, g_ct, g_ac, g_len = one_run(full_budget)
    for s in range(slots):
        solo = np.zeros(slots, np.int32)
        solo[s] = full_budget[s]
        em, ct, ac, ln = one_run(solo)
        assert ct[s] == g_ct[s], (s, ct, g_ct)
        assert ac[s] == g_ac[s]
        np.testing.assert_array_equal(em[s], g_em[s])
        assert ln[s] == g_len[s]
    # the ragged contract itself: counts bounded by the slot's own draft
    assert np.all(g_ct <= draft_len + 1)
    assert g_ct[2] == 1  # a 0-draft slot is exactly one decode step
    assert np.all(g_ac <= draft_len)


def test_ragged_zero_draft_row_matches_decode_step(tiny_model_kwargs):
    """A draft_len == 0 row through the RAGGED verify must emit exactly
    the greedy decode_step token — pad drafts can never leak in."""
    cfg, engine = _engine(tiny_model_kwargs, slots=2, spec_len=3)
    params = _params(cfg, engine)
    prompts = [[1, 2, 3, 4], [5, 6, 7]]

    def park():
        cache = engine.init_cache()
        for s, p in enumerate(prompts):
            kv, _ = engine.prefill(params, p)
            cache = engine.insert(cache, kv, s, len(p))
        return cache

    args = (np.full(2, -1, np.int32), np.full(2, 8, np.int32),
            np.zeros(2, np.float32), np.zeros(2, np.int32),
            np.ones(2, np.float32))
    key = jax.random.PRNGKey(0)
    _, want, _ = engine.decode_step(
        params, park(), np.asarray([4, 7], np.int32), key, *args[2:])
    want = np.asarray(want)  # greedy: the sampled token IS the argmax
    tokens = np.asarray([[4, 111, 112, 113], [7, 114, 115, 116]], np.int32)
    r = engine.verify(
        params, park(), tokens, key, *args,
        draft_len=np.zeros(2, np.int32))
    emitted, counts = r.tokens, r.counts
    counts = np.asarray(counts)
    np.testing.assert_array_equal(counts, [1, 1])
    np.testing.assert_array_equal(np.asarray(emitted)[:, 0], want)


# --------------------------------------------------------------------------- #
# the learned drafter (EAGLE-style head over the target's hidden state)
# --------------------------------------------------------------------------- #


def _np_head(params_np, h, eps):
    """The target's logits path over a hidden state, in numpy: final
    RMSNorm then the shared lm_head — the oracle for the return_hidden
    hook's contract."""
    w = params_np["final_norm"].astype(np.float64)
    x = h.astype(np.float64)
    x = x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w
    return x @ params_np["lm_head"].astype(np.float64)


def test_return_hidden_is_the_logits_producing_state(tiny_model_kwargs):
    """The hook's contract, pinned against the model's own head: the
    hidden state every dispatch returns is the one whose (final-norm +
    lm_head) logits produced that slot's last emitted token — prefill,
    decode_block, and verify (ragged rows included)."""
    cfg, engine = _engine(tiny_model_kwargs, slots=2, spec_len=3,
                          drafter="learned", decode_block_len=4)
    assert engine.return_hidden
    params = _params(cfg, engine)
    params_np = jax.tree.map(np.asarray, jax.device_get(params))
    eps = cfg.model.rms_norm_eps

    # prefill: returned logits == head(returned hidden)
    prompt = [1, 2, 3, 4, 5]
    kv, logits, hid = engine.prefill(params, prompt)
    np.testing.assert_allclose(
        _np_head(params_np, np.asarray(hid), eps)[0],
        np.asarray(logits)[0], rtol=1e-4, atol=1e-4)

    cache = engine.insert(engine.init_cache(), kv, 0, len(prompt))
    kv2, logits2, _ = engine.prefill(params, [9, 8])
    cache = engine.insert(cache, kv2, 1, 2)
    first = np.asarray([int(np.argmax(np.asarray(logits)[0])),
                        int(np.argmax(np.asarray(logits2)[0]))], np.int32)

    # decode_block: argmax(head(hidden)) == the slot's last emitted token
    keys = np.stack([np.asarray(jax.random.PRNGKey(i)) for i in range(4)])
    args = (np.full(2, -1, np.int32), np.asarray([4, 2], np.int32),
            np.zeros(2, np.float32), np.zeros(2, np.int32),
            np.ones(2, np.float32))
    r = engine.decode_block(
        params, cache, first, keys, *args)
    cache, toks, counts, hid = r.cache, r.tokens, r.counts, r.hidden
    toks, counts = np.asarray(toks), np.asarray(counts)
    for s in range(2):
        last = toks[s, counts[s] - 1]
        assert np.argmax(_np_head(params_np,
                                  np.asarray(hid)[s][None], eps)[0]) == last

    # verify (ragged): same invariant, draft lengths [2, 0]
    last_toks = np.asarray([toks[s, counts[s] - 1] for s in range(2)],
                           np.int32)
    tokens = np.zeros((2, 4), np.int32)
    tokens[:, 0] = last_toks
    tokens[0, 1:3] = [7, 7]
    r = engine.verify(
        params, cache, tokens, jax.random.PRNGKey(9),
        np.full(2, -1, np.int32), np.full(2, 8, np.int32),
        np.zeros(2, np.float32), np.zeros(2, np.int32),
        np.ones(2, np.float32), draft_len=np.asarray([2, 0], np.int32))
    cache, emitted, vcounts, vhid = r.cache, r.tokens, r.counts, r.hidden
    emitted, vcounts = np.asarray(emitted), np.asarray(vcounts)
    for s in range(2):
        last = emitted[s, vcounts[s] - 1]
        assert np.argmax(_np_head(params_np,
                                  np.asarray(vhid)[s][None], eps)[0]) == last


@pytest.mark.parametrize("tp", [1, 2])
def test_learned_drafter_greedy_bit_identical(tiny_model_kwargs, tp):
    """Greedy batcher streams with the learned drafter (whatever it
    proposes) must equal the spec-off streams token for token — the
    acceptance rule's guarantee holds for the new drafter + hidden
    plumbing, on tp=1 and a tp=2 mesh."""
    cfg, eng_off = _engine(tiny_model_kwargs, tp=tp)
    _, eng_on = _engine(tiny_model_kwargs, tp=tp, spec_len=3,
                        drafter="learned")
    params = _params(cfg, eng_off)
    reqs = [Request("a", [1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=17),
            Request("b", [9, 8, 7], max_new_tokens=6)]
    want = ContinuousBatcher(eng_off, params).run(reqs)
    b = ContinuousBatcher(eng_on, params)
    assert b.drafter.kind == "learned"
    got = b.run(reqs)
    for r in reqs:
        assert got[r.uid].tokens == want[r.uid].tokens, (r.uid, tp)
        assert got[r.uid].drafter == "learned"
    assert b.draft_proposed > 0  # it really drafted


def test_learned_drafter_deterministic_and_head_variant(tiny_model_kwargs):
    """propose_batch is a deterministic function of (hidden, token) —
    the point-mass contract the accept rule assumes — and the optional
    tiny-head params change the proposal function without breaking it."""
    cfg, engine = _engine(tiny_model_kwargs, slots=2, spec_len=4,
                          drafter="learned")
    params = _params(cfg, engine)
    d = LearnedDrafter(engine, params)
    hidden = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 64)).astype(np.float32))
    toks = np.asarray([5, 9], np.int32)
    a = d.propose_batch(toks, hidden, 4)
    b = d.propose_batch(toks, hidden, 4)
    np.testing.assert_array_equal(a, b)
    assert a.shape == (2, 4) and a.dtype == np.int32
    assert np.all((a >= 0) & (a < cfg.model.vocab_size))
    with pytest.raises(ValueError, match="spec_len"):
        d.propose_batch(toks, hidden, 2)
    with pytest.raises(TypeError, match="propose_batch"):
        d.propose(np.asarray([1, 2]), 4)
    # tiny-head variant (the shape checkpoint.load_params would restore)
    head = init_draft_head(jax.random.PRNGKey(1), cfg.model.hidden_size)
    dh = LearnedDrafter(engine, params, head=head)
    c = dh.propose_batch(toks, hidden, 4)
    assert c.shape == (2, 4)
    np.testing.assert_array_equal(c, dh.propose_batch(toks, hidden, 4))
    # a spec-off / hidden-less engine is rejected with the fix named
    _, plain = _engine(tiny_model_kwargs)
    with pytest.raises(ValueError, match="spec"):
        LearnedDrafter(plain, params)
    _, no_hidden = _engine(tiny_model_kwargs, spec_len=3)
    with pytest.raises(ValueError, match="return_hidden"):
        LearnedDrafter(no_hidden, params)


# --------------------------------------------------------------------------- #
# the spec controller: hysteresis, convergence, switching, cost model
# --------------------------------------------------------------------------- #


def _controller(reg=None, *, kinds=("ngram",), gmax=4, block_len=8, **kw):
    cfg = SpecControllerConfig(enabled=True, **kw)
    reg = reg if reg is not None else MetricsRegistry()
    c = SpecController(cfg, reg, slots=1, max_spec_len=gmax,
                       block_len=block_len, kinds=kinds)
    c.reset(0)
    return c, reg


def _feed(c, reg, proposed, accepted):
    """One round's worth of counters into the registry (what the batcher
    writes), then the controller's policy tick."""
    reg.counter("picotron_slot_draft_proposed_total",
                slot="0").inc(proposed)
    reg.counter("picotron_slot_draft_accepted_total",
                slot="0").inc(accepted)
    c.record(0, proposed, accepted)
    c.after_round(0)


def test_controller_hysteresis_no_oscillation():
    """Adversarial accept-rate flip-flop traffic: full-accept windows
    alternating with zero-accept windows. The direction alternates every
    evaluation, the hysteresis streak never completes, and spec_len must
    NOT move — not once."""
    c, reg = _controller(window=4, hysteresis=2, cooloff=1000)
    g0 = int(c.lens()[0])
    for i in range(40):
        _feed(c, reg, 4, 4 if i % 2 == 0 else 0)
        assert int(c.lens()[0]) == g0, f"oscillated at round {i}"
    assert not c.decisions  # no ramp was ever applied


def test_controller_ramps_down_to_off_and_probes():
    """Persistently hard traffic: halve per hysteresis streak down to 1,
    then (single drafter) OFF; after cooloff idle rounds the controller
    re-probes with a 1-token draft."""
    c, reg = _controller(window=4, hysteresis=2, low=0.25, cooloff=3)
    seen = [int(c.lens()[0])]
    for _ in range(30):
        if int(c.lens()[0]) == 0:
            break
        _feed(c, reg, max(int(c.lens()[0]), 1), 0)
        seen.append(int(c.lens()[0]))
    assert seen[0] == 4 and 2 in seen and 1 in seen
    assert int(c.lens()[0]) == 0
    assert c.decisions.get("spec_off") == 1
    # monotone on persistent signal: never back up mid-descent
    assert all(a >= b for a, b in zip(seen, seen[1:]))
    for _ in range(3):  # cooloff rounds at 0
        c.after_round(0)
    assert int(c.lens()[0]) == 1  # the probe
    assert c.decisions.get("probe") == 1


def test_controller_ramps_up_on_easy_traffic():
    c, reg = _controller(window=2, hysteresis=2, target=0.5, cooloff=1000)
    # drive down to 1 first
    while int(c.lens()[0]) > 1:
        _feed(c, reg, max(int(c.lens()[0]), 1), 0)
    # then full acceptance doubles back to the ceiling
    for _ in range(20):
        g = int(c.lens()[0])
        _feed(c, reg, max(g, 1), max(g, 1))
    assert int(c.lens()[0]) == 4
    assert c.decisions.get("ramp_up", 0) >= 2


def test_controller_switches_drafter_before_giving_up():
    """With a learned primary and the n-gram fallback registered, a slot
    losing at spec_len 1 tries the OTHER drafter before turning
    speculation off."""
    c, reg = _controller(window=2, hysteresis=1, kinds=("learned", "ngram"),
                         cooloff=1000)
    assert c.drafter_kinds()[0] == "learned"
    switched = False
    for _ in range(30):
        if int(c.lens()[0]) == 0:
            break
        _feed(c, reg, max(int(c.lens()[0]), 1), 0)
        if c.drafter_kinds()[0] == "ngram":
            switched = True
    assert switched and c.decisions.get("switch_drafter") == 1
    assert int(c.lens()[0]) == 0  # both tried and bad -> off


def test_controller_latency_term_vetoes_losing_speculation():
    """Once the dispatch-latency histograms hold enough samples, a
    measured verify cost that can't beat blocked decode forces the ramp
    DOWN even at full acceptance — speculation must PAY, not just
    accept."""
    c, reg = _controller(window=2, hysteresis=1, latency_min_samples=4,
                         block_len=8)
    hv = reg.histogram("picotron_dispatch_seconds",
                       "dispatch wall time incl. host sync, by kind",
                       kind="verify")
    hd = reg.histogram("picotron_dispatch_seconds",
                       "dispatch wall time incl. host sync, by kind",
                       kind="decode")
    for _ in range(8):
        hv.observe(0.2)   # a verify costs 0.2s for <= 5 tokens
        hd.observe(0.08)  # a block of 8 tokens costs 0.08s
    for _ in range(10):
        if int(c.lens()[0]) == 0:
            break
        _feed(c, reg, max(int(c.lens()[0]), 1), max(int(c.lens()[0]), 1))
    assert int(c.lens()[0]) == 0  # full acceptance, measured loss -> off


class RegimeDrafter(Drafter):
    """Per-request regimes for the acceptance test: requests with a
    script (the 'repetitive' regime) get ORACLE proposals — the known
    greedy future — while scriptless ('random') requests get junk, so
    the two regimes' accept rates are deterministic extremes."""

    kind = "ngram"
    stateful = True

    def __init__(self, scripts):
        self.scripts = scripts  # uid -> prompt + expected tokens

    def propose(self, history, n, ctx=None):
        h = np.asarray(history, np.int32).reshape(-1)
        script = self.scripts.get(ctx)
        out = np.zeros(n, np.int32)
        if script is None:  # junk: varies so it can't accidentally loop
            return (h[-1] + 1 + np.arange(n, dtype=np.int32)) % 251
        tail = script[h.size: h.size + n]
        out[: len(tail)] = tail
        return out


def test_controller_mixed_workload_convergence(tiny_model_kwargs):
    """THE acceptance run (through the real batcher): on a mixed
    workload, repetitive-regime slots converge to spec_len > 0 with
    per-request dispatches/token strictly below the spec-off per-token
    baseline of 1, random-regime slots converge to spec_len == 0 within
    the run, and every greedy stream stays BIT-IDENTICAL to spec-off."""
    raw = make_config(tiny_model_kwargs, seq=MAX_LEN).to_dict()
    raw["inference"].update(dict(
        spec_len=4,
        spec_controller=dict(enabled=True, window=4, hysteresis=2,
                             target=0.6, low=0.3, cooloff=10_000)))
    cfg = Config.from_dict(raw)
    eng_off = InferenceEngine(cfg, slots=4, max_seq_len=MAX_LEN,
                              spec_len=0)
    params = _params(cfg, eng_off)

    def reqs():
        return [Request("rep0", [1, 2, 3, 1, 2, 3], max_new_tokens=48),
                Request("rep1", [5, 6, 5, 6, 5], max_new_tokens=48),
                Request("rand0", [11, 23, 7], max_new_tokens=30),
                Request("rand1", [42, 9, 31, 8], max_new_tokens=30)]

    want = ContinuousBatcher(eng_off, params).run(reqs())
    scripts = {u: list(r.prompt) + want[u].tokens
               for u, r in ((q.uid, q) for q in reqs())
               if u.startswith("rep")}
    eng_on = InferenceEngine(cfg, slots=4, max_seq_len=MAX_LEN)
    b = ContinuousBatcher(eng_on, params, drafter=RegimeDrafter(scripts))
    assert b.controller is not None
    got = b.run(reqs())
    for u, r in want.items():
        assert got[u].tokens == r.tokens, u  # greedy unchanged, always
    for u in ("rep0", "rep1"):
        assert got[u].spec_len_final > 0, (u, got[u])
        dpt = got[u].dispatches / len(got[u].tokens)
        assert dpt < 1.0, (u, dpt)  # strictly beats spec-off per-token
    for u in ("rand0", "rand1"):
        assert got[u].spec_len_final == 0, (u, got[u])
    # decisions + effective length landed in stats and on the scrape
    st = b.stats()
    assert st["spec_controller"].get("spec_off", 0) >= 2
    assert "spec_len_effective" in st
    b.refresh_gauges()
    prom = b.obs.registry.prometheus()
    assert "picotron_spec_accept_rate" in prom
    assert "picotron_spec_len" in prom


def test_controller_loop_closes_with_obs_disabled(tiny_model_kwargs):
    """``obs.enabled: false`` swaps the registry for null instruments —
    the controller must still close its loop off the internal shadow
    tallies (and greedy output stays identical, as everywhere)."""
    raw = make_config(tiny_model_kwargs, seq=MAX_LEN).to_dict()
    raw["inference"].update(dict(
        spec_len=4,
        spec_controller=dict(enabled=True, window=4, hysteresis=2)))
    raw["obs"] = {"enabled": False}
    cfg = Config.from_dict(raw)
    eng_off = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                              spec_len=0)
    eng_on = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN)
    params = _params(cfg, eng_off)
    reqs = [Request("a", [1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=20),
            Request("b", [11, 23, 7], max_new_tokens=16)]
    want = ContinuousBatcher(eng_off, params).run(reqs)
    b = ContinuousBatcher(eng_on, params)
    got = b.run(reqs)
    for r in reqs:
        assert got[r.uid].tokens == want[r.uid].tokens, r.uid
    assert b.controller.decisions  # it DECIDED, blind registry and all


def test_controller_on_greedy_identical_with_real_ngram(tiny_model_kwargs):
    """Controller enabled with the REAL n-gram drafter (accepts and
    rejections both occur, lengths ramp): greedy streams still equal
    spec-off bit for bit — the ragged verify preserves the greedy
    chain no matter what the policy loop decides."""
    raw = make_config(tiny_model_kwargs, seq=MAX_LEN).to_dict()
    raw["inference"].update(dict(
        spec_len=4,
        spec_controller=dict(enabled=True, window=4, hysteresis=2)))
    cfg = Config.from_dict(raw)
    eng_off = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                              spec_len=0)
    eng_on = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN)
    params = _params(cfg, eng_off)
    reqs = [Request("a", [1, 2, 3, 1, 2, 3, 1, 2], max_new_tokens=20),
            Request("b", [9, 8, 7], max_new_tokens=9)]
    want = ContinuousBatcher(eng_off, params).run(reqs)
    got = ContinuousBatcher(eng_on, params).run(reqs)
    for r in reqs:
        assert got[r.uid].tokens == want[r.uid].tokens, r.uid
