"""Per-channel int8 weight quantization: the fused dequant matmul and its
end-to-end wiring (ops/pallas/quant_matmul.py, ``inference.weight_dtype``).

The discipline mirrors the int8 KV cache's (test_decode_kernel.py):

- kernel-level parity: the Pallas kernel (interpret mode — the CPU tier-1
  gate; the same program lowers to Mosaic on a chip) and the XLA fallback
  are both allclose to the fake-quant reference
  ``x @ dequantize_weight(q, s)`` across shapes, dtypes, and non-dividing
  tile sizes;
- the no-materialization proof: ``dequantize_weight`` is monkeypatched to
  raise and full int8-weight generations still run — the serving path
  never builds a dequantized copy of any weight, on either impl;
- engine-level equivalence: an int8 engine's generations are IDENTICAL to
  a bf16 engine fed the fake-quant reference tree (the quantization error
  is in both, so any difference is the fused pipeline itself) across
  decode_block / speculative verify / chunked prefill, dense AND flash
  attends, contiguous AND paged KV layouts, tp=1 and tp=2, greedy pinned
  through the full ContinuousBatcher.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_config
from picotron_tpu.config import Config
from picotron_tpu.inference import ContinuousBatcher, InferenceEngine, Request
from picotron_tpu.models import llama
from picotron_tpu.ops.pallas import quant_matmul as qm

MAX_LEN = 96


# --------------------------------------------------------------------------- #
# quantization + kernel parity (direct calls)
# --------------------------------------------------------------------------- #


def test_quantize_weight_per_channel_error_bound():
    """Dequantized weights sit within the per-channel absmax grid: error
    at most half a quantization step (scale/2) per element, and an
    all-zero channel round-trips exactly (uneven-pp pad rows)."""
    rng = np.random.default_rng(0)
    w = rng.normal(size=(64, 40)).astype(np.float32)
    w[:, 7] = 0.0  # a dead channel
    w[:, 11] = w[:, 11] * 1e-12  # denormal-tiny channel: the clamp edge —
    # the STORED scale must be the clamped divisor, or dequantization
    # collapses to zero while claiming a true tiny scale
    qw = qm.quantize_weight(jnp.asarray(w))
    deq = np.asarray(qm.dequantize_weight(qw["q"], qw["s"]))
    step = np.asarray(qw["s"])  # one scale per output channel
    assert np.all(np.abs(deq - w) <= step[None, :] / 2 + 1e-8)
    np.testing.assert_array_equal(deq[:, 7], 0.0)
    # the host (numpy) variant is bit-identical — the checkpoint
    # streaming path quantizes exactly like the in-memory one
    qh = qm.quantize_weight_host(w)
    np.testing.assert_array_equal(np.asarray(qw["q"]), qh["q"])
    np.testing.assert_array_equal(np.asarray(qw["s"]), qh["s"])


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("M,K,N", [(1, 32, 48), (3, 64, 40), (16, 128, 96),
                                   (5, 96, 256)])
def test_kernel_and_fallback_match_fakequant(M, K, N, dtype, tol):
    """Pallas (interpret) and the XLA fallback against the fake-quant
    reference: odd M (sublane padding), non-pow2 N/K (halve-until-divides
    tiling), fp32 and bf16 activations."""
    rng = np.random.default_rng(1)
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.normal(size=(M, K)).astype(np.float32)).astype(dt)
    w = jnp.asarray(rng.normal(size=(K, N)).astype(np.float32))
    qw = qm.quantize_weight(w)
    ref = np.asarray(x.astype(jnp.float32)
                     @ qm.dequantize_weight(qw["q"], qw["s"]), np.float32)
    out_p = qm.quant_matmul(x, qw["q"], qw["s"], interpret=True)
    out_x = qm.quant_matmul(x, qw["q"], qw["s"], impl="xla")
    # the output dtype follows x (the dense path's same-dtype promotion)
    assert out_p.dtype == dt and out_x.dtype == dt
    got_p = np.asarray(out_p, np.float32)
    got_x = np.asarray(out_x, np.float32)
    np.testing.assert_allclose(got_p, ref, rtol=tol, atol=tol)
    np.testing.assert_allclose(got_x, ref, rtol=tol, atol=tol)
    # leading batch dims flatten through
    x3 = x.reshape(1, M, K)
    got3 = np.asarray(qm.quant_matmul(x3, qw["q"], qw["s"], impl="xla"),
                      np.float32)
    np.testing.assert_array_equal(got3[0], got_x)


def test_small_tile_fallback_blocks():
    """Tiny non-dividing dims degrade tile sizes instead of crashing —
    the tiny CPU test models' shapes."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(24, 24)).astype(np.float32))
    qw = qm.quantize_weight(w)
    ref = np.asarray(x @ qm.dequantize_weight(qw["q"], qw["s"]))
    got = np.asarray(qm.quant_matmul(x, qw["q"], qw["s"], interpret=True))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_quant_matmul_validates():
    x = jnp.zeros((2, 8))
    w = jnp.zeros((8, 8))  # NOT int8
    s = jnp.zeros((8,))
    with pytest.raises(ValueError, match="int8"):
        qm.quant_matmul(x, w, s)
    with pytest.raises(ValueError, match="impl"):
        qm.quant_matmul(x, w.astype(jnp.int8), s, impl="dense")


def test_no_dequantized_weight_materialization(monkeypatch):
    """Both impls must consume int8 bytes + scales directly — routing
    through ``dequantize_weight`` (the tests-only whole-tensor fp32
    materialization) raises. The test_decode_kernel.py discipline."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(4, 64)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(64, 48)).astype(np.float32))
    qw = qm.quantize_weight(w)
    ref = np.asarray(x @ qm.dequantize_weight(qw["q"], qw["s"]))

    def boom(*a, **kw):
        raise AssertionError("quant matmul materialized a dequantized copy")

    monkeypatch.setattr(qm, "dequantize_weight", boom)
    for kw in (dict(interpret=True), dict(impl="xla")):
        got = np.asarray(qm.quant_matmul(x, qw["q"], qw["s"], **kw))
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# tree helpers + pspecs
# --------------------------------------------------------------------------- #


def _params(cfg):
    return jax.jit(lambda k: llama.init_params(k, cfg.model))(
        jax.random.PRNGKey(0))


def test_quantize_params_tree_and_bytes(tiny_model_kwargs):
    """Only the seven projections + lm_head quantize; embeddings/norms
    stay full precision; the pspec tree mirrors the quantized tree's
    structure; int8 bytes come in at <= 55% of the bf16 tree's."""
    cfg = make_config(tiny_model_kwargs, dtype="bfloat16")
    params = _params(cfg)
    qp = llama.quantize_params(params)
    for k in llama.QUANT_WEIGHT_LEAVES:
        leaf = qp["layers"][k]
        assert qm.is_quant_weight(leaf)
        assert leaf["q"].dtype == jnp.int8
        assert leaf["s"].dtype == jnp.float32
        assert leaf["s"].shape == leaf["q"].shape[:-2] + leaf["q"].shape[-1:]
    assert qm.is_quant_weight(qp["lm_head"])
    for k in ("embed", "final_norm"):
        assert qp[k].dtype == params[k].dtype
    for k in ("attn_norm", "mlp_norm"):
        assert not qm.is_quant_weight(qp["layers"][k])
    # the quantized pspec tree has the quantized params' structure
    specs = llama.param_pspecs(cfg.model, weight_dtype="int8")
    assert (jax.tree.structure(qp)
            == jax.tree.structure(specs,
                                  is_leaf=lambda x: not isinstance(x, dict)))
    # the quantized-leaf bytes come in at <= 55% of their bf16 form (the
    # tiny model's full-tree ratio is dominated by the deliberately
    # full-precision embedding; at the 7B geometry — checked below over
    # the trees' shapes — the whole tree lands at ~51%)
    def mat_bytes(tree):
        leaves = [tree["layers"][k] for k in llama.QUANT_WEIGHT_LEAVES]
        leaves.append(tree["lm_head"])
        return sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(leaves))

    ratio = mat_bytes(qp) / mat_bytes(params)
    assert ratio <= 0.55, ratio
    assert llama.param_bytes(qp) < llama.param_bytes(params)

    # Llama-2-7B's geometry, shapes alone: the two trees as the program
    # would build them
    m7b = make_config(dict(
        num_hidden_layers=32, num_attention_heads=32,
        num_key_value_heads=32, hidden_size=4096, intermediate_size=11008,
        vocab_size=32000, max_position_embeddings=4096, dtype="bfloat16",
        attention_impl="sdpa")).model
    key = jax.random.PRNGKey(0)
    dense7b = jax.eval_shape(lambda k: llama.init_params(k, m7b), key)
    int8_7b = jax.eval_shape(
        lambda k: llama.quantize_params(llama.init_params(k, m7b)), key)
    assert llama.param_bytes(int8_7b) <= 0.55 * llama.param_bytes(dense7b)
    # fake-quant round trip restores the dense structure and dtype
    fq = llama.dequantize_params(qp, jnp.bfloat16)
    assert jax.tree.structure(fq) == jax.tree.structure(params)
    assert fq["layers"]["wq"].dtype == jnp.bfloat16


def test_fsdp_rejects_quantized_pspecs(tiny_model_kwargs):
    cfg = make_config(tiny_model_kwargs)
    with pytest.raises(ValueError, match="fsdp"):
        llama.param_pspecs(cfg.model, fsdp=True, weight_dtype="int8")


def test_config_and_engine_validate_weight_dtype(tiny_model_kwargs):
    """Bad weight_dtype strings fail loudly at config load and engine
    build, naming the fix."""
    cfg = make_config(tiny_model_kwargs, seq=MAX_LEN)
    raw = cfg.to_dict()
    raw["inference"]["weight_dtype"] = "fp8"
    with pytest.raises(ValueError, match="weight_dtype"):
        Config.from_dict(raw)
    with pytest.raises(ValueError, match="weight_dtype"):
        InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                        weight_dtype="fp8")


# --------------------------------------------------------------------------- #
# engine-level equivalence: int8 vs the fake-quant bf16 reference
# --------------------------------------------------------------------------- #


def _engines(tiny_model_kwargs, tp=1, **kw):
    """(int8 engine + quantized params, dense engine + fake-quant params)
    — the pair every equivalence test compares. Both trees carry the SAME
    quantization error; only the matmul plumbing differs."""
    cfg = make_config(tiny_model_kwargs, tp=tp, seq=MAX_LEN)
    eng_q = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                            weight_dtype="int8", **kw)
    eng_d = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                            weight_dtype="bf16", **kw)
    params = _params(cfg)
    qp = llama.quantize_params(params)
    fq = llama.dequantize_params(qp, jnp.dtype(cfg.model.dtype))
    return ((eng_q, eng_q.shard_params(qp)),
            (eng_d, eng_d.shard_params(fq)))


@pytest.mark.parametrize("attend_impl", ["dense", "flash"])
@pytest.mark.parametrize("kv_layout", ["contiguous", "paged"])
def test_decode_block_matches_fakequant(tiny_model_kwargs, attend_impl,
                                        kv_layout, monkeypatch):
    """The blocked-decode dispatch across attend kernels and KV layouts —
    with ``dequantize_weight`` armed to raise, so the whole int8 decode
    provably never materializes a weight."""
    outs = []
    for i, (eng, params) in enumerate(_engines(
            tiny_model_kwargs, attend_impl=attend_impl,
            kv_layout=kv_layout, decode_block_len=4)):
        if i == 0:  # the int8 engine runs under the no-materialize trap
            monkeypatch.setattr(qm, "dequantize_weight", _boom)
        else:
            monkeypatch.undo()
        cache = eng.init_cache()
        kv, logits = eng.prefill(params, list(range(1, 9)))
        cache = eng.insert(cache, kv, 0, 8)
        toks = np.array([int(np.argmax(np.asarray(logits)[0])), 0], np.int32)
        keys = jnp.stack([jax.random.PRNGKey(7)] * 4)
        r = eng.decode_block(
            params, cache, toks, keys, np.full(2, -1, np.int32),
            np.array([8, 0], np.int32), np.zeros(2, np.float32),
            np.zeros(2, np.int32), np.ones(2, np.float32))
        cache, blk, counts = r.cache, r.tokens, r.counts
        outs.append((int(toks[0]), np.asarray(blk), np.asarray(counts)))
    assert outs[0][0] == outs[1][0]  # prefill argmax
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    np.testing.assert_array_equal(outs[0][2], outs[1][2])


def _boom(*a, **kw):
    raise AssertionError("serving path materialized a dequantized weight")


@pytest.mark.parametrize("attend_impl", ["dense", "flash"])
def test_verify_matches_fakequant(tiny_model_kwargs, attend_impl):
    """The speculative verify dispatch (S>1, B>1): same emitted tokens,
    counts, accepted-draft counts, and length pointers."""
    outs = []
    for eng, params in _engines(tiny_model_kwargs, spec_len=3,
                                attend_impl=attend_impl):
        cache = eng.init_cache()
        for slot in (0, 1):
            kv, _ = eng.prefill(params, list(range(1 + slot, 9 + slot)))
            cache = eng.insert(cache, kv, slot, 8)
        tokens = np.array([[3, 5, 7, 9], [4, 6, 8, 10]], np.int32)
        r = eng.verify(
            params, cache, tokens, jax.random.PRNGKey(3),
            np.full(2, -1, np.int32), np.full(2, 8, np.int32),
            np.zeros(2, np.float32), np.zeros(2, np.int32),
            np.ones(2, np.float32))
        cache, emitted = r.cache, r.tokens
        counts, accepted = r.counts, r.accepted
        outs.append(tuple(np.asarray(x) for x in
                          (emitted, counts, accepted, cache["lengths"])))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("attend_impl", ["dense", "flash"])
def test_chunked_prefill_matches_fakequant(tiny_model_kwargs, attend_impl):
    """The chunked-prefill dispatch (B=1, S=chunk, ragged final chunk):
    final logits agree across the int8 and fake-quant engines AND with
    the int8 one-shot prefill."""
    prompt = [(5 * i + 2) % 199 + 1 for i in range(20)]
    logits = []
    for eng, params in _engines(tiny_model_kwargs, prefill_chunk=8,
                                attend_impl=attend_impl):
        cache, last = eng.prefill_chunked(params, eng.init_cache(),
                                          prompt, slot=1)
        assert int(np.asarray(cache["lengths"])[1]) == len(prompt)
        logits.append(np.asarray(last)[0])
        oneshot = np.asarray(eng.prefill(params, prompt)[1])[0]
        np.testing.assert_allclose(last[0], oneshot, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(logits[0], logits[1], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("tp", [1, 2])
def test_batcher_generations_match_fakequant(tiny_model_kwargs, tp):
    """Greedy generations pinned through the full ContinuousBatcher on
    tp=1 AND tp=2 — the sharded path, where int8 values and their
    per-channel scales split over 'tp' together. Identical tokens and
    finish reasons for every request."""
    results = []
    for eng, params in _engines(tiny_model_kwargs, tp=tp):
        reqs = [Request(uid=f"r{i}", prompt=list(range(1 + i, 7 + i)),
                        max_new_tokens=10) for i in range(3)]
        results.append(ContinuousBatcher(eng, params, seed=0).run(reqs))
    for uid in results[0]:
        assert results[0][uid].tokens == results[1][uid].tokens, uid
        assert (results[0][uid].finish_reason
                == results[1][uid].finish_reason)


def test_tp2_shards_scales_with_channels(tiny_model_kwargs):
    """A tp=2 engine's placed quantized tree: each wq shard carries the
    GLOBAL quantization's values and scales for its own channel slice —
    per-channel quantization commutes with the column split."""
    cfg = make_config(tiny_model_kwargs, tp=2, seq=MAX_LEN)
    eng = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                          weight_dtype="int8")
    qp = llama.quantize_params(_params(cfg))
    placed = eng.shard_params(qp)
    wq = placed["layers"]["wq"]
    # the scale leaf is sharded over tp on its channel axis
    shard = wq["s"].sharding.shard_shape(wq["s"].shape)
    assert shard[-1] == wq["s"].shape[-1] // 2
    np.testing.assert_array_equal(np.asarray(wq["q"]),
                                  np.asarray(qp["layers"]["wq"]["q"]))
    np.testing.assert_array_equal(np.asarray(wq["s"]),
                                  np.asarray(qp["layers"]["wq"]["s"]))


def test_int8_generations_allclose_bf16_logits(tiny_model_kwargs):
    """Against the TRUE full-precision weights (not the fake-quant
    reference) the contract is allclose logits within the absmax grid:
    prefill logits of the int8 engine sit near the dense engine's, with
    the error bounded by the quantization step — the same tolerance
    discipline as the checkpoint roundtrip test."""
    cfg = make_config(tiny_model_kwargs, seq=MAX_LEN)
    params = _params(cfg)
    eng_d = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN)
    dense = np.asarray(eng_d.prefill(eng_d.shard_params(params),
                                     list(range(1, 9)))[1])
    eng_q = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                            weight_dtype="int8")
    quant = np.asarray(eng_q.prefill(
        eng_q.shard_params(llama.quantize_params(params)),
        list(range(1, 9)))[1])
    # int8 carries ~0.4% relative error per matmul; across 4 tiny layers
    # the logits stay within a loose-but-meaningful band
    np.testing.assert_allclose(quant, dense, rtol=0.1, atol=0.1)
    assert int(np.argmax(quant[0])) == int(np.argmax(dense[0]))
