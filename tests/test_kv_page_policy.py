"""Per-page KV quantization policy (``inference.kv_page_policy: hot_bf16``).

The paged pool keeps TWO representations of every written row — full
precision and int8 + per-row scales — and a per-page flag, recomputed from
the host allocator's refcounts before each dispatch, selects which one
the attend READS: pages with more than one holder (radix-shared prefixes,
forked slots) stay full precision, exclusively-held pages (cold unique
tails) read as int8. This file pins the contract:

- **dense ≡ flash**: both read paths consume the same flags and bytes, so
  paged generations are bit-identical across impls (mirroring the int8
  discipline in tests/test_decode_kernel.py);
- **hot pages really are hot**: under a shared prefix, the shared pages'
  flags read full-precision while exclusive tail pages read int8;
- **allclose vs uniform** at int8-level tolerance with strictly fewer
  cache bytes read per attend walk over the live pages;
- **validation** rejects the policy off the paged layout (and over a
  uniformly int8 cache) with the fix named, at both the config and the
  engine-kwarg layer.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import make_config
from picotron_tpu.config import Config
from picotron_tpu.inference import InferenceEngine
from picotron_tpu.inference.batcher import ContinuousBatcher, Request
from picotron_tpu.models import llama

MAX_LEN = 96
# two prompts sharing a 14-token prefix (page_len 8 -> one full shared
# page + a shared partial) plus a radix re-hit of the first prompt
PROMPTS = [
    list(range(1, 19)),
    list(range(1, 15)) + [41, 42],
    list(range(1, 19)),
]


def _engine(tiny_model_kwargs, **kw):
    cfg = make_config(tiny_model_kwargs, tp=1, seq=MAX_LEN)
    eng = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                          kv_layout="paged", kv_page_len=8,
                          decode_block_len=2, **kw)
    params = eng.shard_params(jax.jit(
        lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
    return eng, params


def _generate(tiny_model_kwargs, **kw):
    eng, params = _engine(tiny_model_kwargs, **kw)
    b = ContinuousBatcher(eng, params, seed=3)
    reqs = [Request(f"r{i}", p, max_new_tokens=8)
            for i, p in enumerate(PROMPTS)]
    out = b.run(reqs)
    assert all(r.finish_reason == "length" for r in out.values())
    return {u: r.tokens for u, r in out.items()}, eng


def test_policy_dense_equals_flash(tiny_model_kwargs):
    """Both read paths consume the same per-page flags, so generations
    are bit-identical — the wiring proof that the mixed read reaches the
    dense gather AND the flash DMA walk."""
    dense, _ = _generate(tiny_model_kwargs, kv_page_policy="hot_bf16",
                         attend_impl="dense")
    flash, _ = _generate(tiny_model_kwargs, kv_page_policy="hot_bf16",
                         attend_impl="flash")
    assert dense == flash


def _read_bytes_a_row(eng):
    """What an attend walk reads for one live row of one layer, from the
    leaves the engine keeps and the flags it ships: both K and V at the
    cache's width where the page reads full precision, the int8 values
    and their scales where it reads cold."""
    leaves = jax.eval_shape(eng._init_cache_jit)  # shapes alone, no reset

    def row(*names):  # leaves are [layers, pages, page_len, ...]
        return sum(int(np.prod(leaves[n].shape[3:])) * leaves[n].dtype.itemsize
                   for n in names)

    full = row("k", "v")
    if not eng.page_policy:
        return float(full)
    cold = row("k_q", "v_q", "k_scale", "v_scale")
    assert cold < full  # the int8 row is the narrower one by construction
    live = np.flatnonzero(eng.paged.pool.refs[1:] > 0) + 1
    q = float(np.mean(eng.paged.quant_flags()[live]))
    return q * cold + (1.0 - q) * full


def test_policy_allclose_uniform_with_fewer_bytes(tiny_model_kwargs):
    """hot_bf16 generations stay within int8 tolerance of the uniform
    full-precision cache (here: token-identical on the tiny model), and
    the bytes an attend walk reads over the live pages strictly shrink."""
    uni, ue = _generate(tiny_model_kwargs, kv_page_policy="uniform",
                        attend_impl="flash")
    hot, he = _generate(tiny_model_kwargs, kv_page_policy="hot_bf16",
                        attend_impl="flash")
    assert uni == hot  # int8 tails don't move the tiny model's argmax
    assert _read_bytes_a_row(he) < _read_bytes_a_row(ue)
    stats = he.paged.stats()
    assert stats["kv_pages_quant"] >= 1  # cold tails exist and are int8


def test_shared_prefix_pages_read_full_precision(tiny_model_kwargs):
    """Mid-run flag check: admit two prefix-sharing requests, then look
    at the flags the next dispatch would ship — shared prefix pages hot
    (flag 0), exclusively-held pages cold (flag 1)."""
    eng, params = _engine(tiny_model_kwargs, kv_page_policy="hot_bf16")
    cache = eng.init_cache()
    cache, _, _, cached0 = eng.prefill_paged(params, cache, PROMPTS[0], 0)
    cache, _, _, cached1 = eng.prefill_paged(params, cache, PROMPTS[1], 1)
    assert cached0 == 0 and cached1 > 0  # the second request shared pages
    # the decode pre-write COWs each slot's tail page off the radix-shared
    # prefix — from here the pool holds BOTH shared prefix pages and
    # exclusively-owned tails, the mix the policy exists for
    cache = eng._pre_write(cache, 2, budget=np.array([2, 2]))
    flags = eng.paged.quant_flags()
    refs = eng.paged.pool.refs
    # every multi-holder page reads full precision, every exclusive live
    # page reads int8 — the flag IS the refcount rule
    assert np.all(flags[refs > 1] == 0)
    live_exclusive = (refs == 1)
    live_exclusive[0] = False  # NULL page is metadata, never read
    shared = int(np.sum(refs[1:] > 1))
    assert shared >= 1 and int(np.sum(flags[live_exclusive])) >= 1
    # the attend consumes exactly these flags (shipped by _sync_tables)
    np.testing.assert_array_equal(np.asarray(cache["page_quant"]), flags)


def test_policy_dual_write_keeps_representations_consistent(
        tiny_model_kwargs):
    """Every written page carries BOTH representations: the int8 leaves
    dequantize back to the full-precision leaves within quantization
    error, for every live page (so a flag flip mid-stream can never read
    stale bytes)."""
    from picotron_tpu.inference import kv_cache

    eng, params = _engine(tiny_model_kwargs, kv_page_policy="hot_bf16")
    b = ContinuousBatcher(eng, params, seed=3)
    b.run([Request("a", PROMPTS[0], max_new_tokens=6)])
    cache = b._cache
    refs = eng.paged.pool.refs
    live = np.flatnonzero(refs[1:] > 0) + 1
    k = np.asarray(cache["k"])[:, live].astype(np.float32)
    kq = np.asarray(kv_cache.dequantize_kv(
        jnp.asarray(np.asarray(cache["k_q"])[:, live]),
        jnp.asarray(np.asarray(cache["k_scale"])[:, live]), jnp.float32))
    np.testing.assert_allclose(kq, k, atol=2e-2, rtol=2e-2)


def test_all_rungs_on_tp2(tiny_model_kwargs):
    """The whole PR-11 ladder at once on a tp=2 dryrun mesh — pipelined
    flash DMA over mixed-precision pages with the sampling epilogue —
    emits the same streams as the host-sampling run (the kv-head axis of
    BOTH pool representations shards over 'tp'; the epilogue draws from
    replicated gathered logits, so every shard agrees)."""
    cfg = make_config(dict(tiny_model_kwargs, num_hidden_layers=2),
                      tp=2, seq=MAX_LEN)
    outs = {}
    for sod in (False, True):
        eng = InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                              kv_layout="paged", kv_page_policy="hot_bf16",
                              attend_impl="flash", sample_on_device=sod,
                              decode_block_len=2)
        params = eng.shard_params(jax.jit(
            lambda k: llama.init_params(k, cfg.model))(jax.random.PRNGKey(0)))
        b = ContinuousBatcher(eng, params, seed=5)
        out = b.run([Request("a", PROMPTS[0], max_new_tokens=6,
                             temperature=0.7, top_k=9),
                     Request("b", PROMPTS[1], max_new_tokens=5)])
        outs[sod] = {u: r.tokens for u, r in out.items()}
    assert outs[False] == outs[True]


def test_policy_validation_names_the_fix(tiny_model_kwargs):
    """Config- and engine-level rejections: wrong layout, int8 conflict,
    unknown policy — each naming the corrective setting."""
    cfg = make_config(tiny_model_kwargs, tp=1, seq=MAX_LEN)
    raw = cfg.to_dict()
    raw["inference"]["kv_page_policy"] = "hot_bf16"
    with pytest.raises(ValueError, match="kv_layout.*paged|paged"):
        Config.from_dict(raw)
    raw["inference"]["kv_layout"] = "paged"
    Config.from_dict(raw)  # the named fix works
    raw["inference"]["kv_cache_dtype"] = "int8"
    with pytest.raises(ValueError, match="int8"):
        Config.from_dict(raw)
    raw["inference"]["kv_cache_dtype"] = "auto"
    raw["inference"]["kv_page_policy"] = "hot_fp64"
    with pytest.raises(ValueError, match="uniform|hot_bf16"):
        Config.from_dict(raw)
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                        kv_page_policy="hot_bf16")
    with pytest.raises(ValueError, match="int8"):
        InferenceEngine(cfg, slots=2, max_seq_len=MAX_LEN,
                        kv_layout="paged", kv_page_policy="hot_bf16",
                        cache_dtype="int8")
