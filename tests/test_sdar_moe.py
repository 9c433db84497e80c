"""The SDAR-MoE block (models/sdar_moe.py) and the engine's rounds of blocks,
at toy size on the CPU with seeded weights, against the plain reference
(benchmarks/reference/sdar_moe.py; the tokens a batcher streams are held to
the published loop in tests/test_sdar_generate.py):
every denoise forward's logits through the cache (a commit forward's are read
by nobody: what it stored is held through the next block's forwards) behind
a one-shot and a chunked block-causal prefill, in float32 and bfloat16; the
confidence rule; the expert share; ``kv_cache.attend``'s band; what the
programs count; and what ``Config.validate`` refuses."""

from functools import lru_cache
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import block_toys
from engine_memo import memoized, worst_rel_err

from picotron_tpu.config import ModelConfig
from picotron_tpu.inference import (ContinuousBatcher, InferenceEngine,
                                    Request, kv_cache, sampling)
from picotron_tpu.models import sdar_moe

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.runners import serve_blocks  # noqa: E402 - the chip's check

NAME = "sdar-30b-a3b-ep8-l12"
# two layers of the toy: 8 heads on 2 K/V heads of 16 (two a cache row), a
# softmax top-4 of 16 with 4 held, blocks of 4 under the dynamic rule
TOY = dict(block_toys.TOYS["sdar_moe"], num_hidden_layers=2, first_layer=2,
           total_layers=8)
MASK = TOY["mask_token_id"]


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_sdar_moe",
        os.path.join(ROOT, "benchmarks", "reference", "sdar_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load_reference()


def make_config(model=None, **sections):
    return block_toys.make_config(
        "sdar_moe", {**TOY, **(model or {})}, seq_length=128, **sections)


@memoized
def make_engine(model=None, **kw):
    cfg = make_config(model)
    engine = InferenceEngine(cfg, slots=3, max_seq_len=128, **{
        "prefill_chunk": 16, "decode_block_len": 8, **kw})
    params = jax.jit(lambda k: sdar_moe.init_params(k, cfg.model))(
        jax.random.PRNGKey(7))
    return cfg, engine, engine.shard_params(params)


def tokens(seed: int, n: int) -> list:
    """``n`` ids that are not the mask's."""
    return [int(t) for t in np.random.default_rng(seed).integers(1, MASK, n)]


# ---- (b) every denoise forward's logits, through the cache ------------------


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-3), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("n_prompt,chunk", [
    (38, 16),  # two whole chunks, a third of one block and 12 pad rows,
               # then a remainder of 2
    (15, 16),  # the one-shot program: 12 of a bucket of 16, a remainder of 3
])
def test_denoise_forwards_match_the_reference(n_prompt, chunk, dtype, tol):
    """The chip's check (benchmarks/runners/serve_blocks.py) at toy size:
    block A (the remainder given) denoised and committed, block B through
    the cache that holds A, block C's first forward; the logits at every
    masked position against the reference's full forward of the sequence
    the program held."""
    _, engine, params = make_engine(dict(dtype=dtype), prefill_chunk=chunk)
    prompt = tokens(n_prompt, n_prompt)
    long, short = serve_blocks.check_forwards(engine, params, prompt, ref)
    given = n_prompt % 4
    assert [int(m.sum()) for _, m, _ in long] == list(
        range(4 - given, 0, -1)) + [4, 3, 2, 1, 4]
    # the second part: the same plan behind the first six tokens, admitted
    # through the one-shot program
    assert [len(held) for held, _, _ in short] == [8, 8, 12, 12, 12, 12, 16]
    forwards = long
    ctx = {"reference": ref, "config": dict(TOY)}
    want = serve_blocks.reference_rows(ctx, params, forwards)
    got = [r for _, masked, logits in forwards for r in logits[masked]]
    assert worst_rel_err(got, want) < tol
    ok, rows = serve_blocks.serve.compare_logits(got, want, tol)
    assert ok and len(rows) == sum(range(5 - given)) + 14
    ok, rows = serve_blocks.logits_check(
        dict(ctx, config=dict(TOY, torch_dtype=dtype)), engine, params,
        prompt)
    # every masked row of both parts, their error as a whole, and the real
    # round: its whole run against the forwards, four streams against it
    assert ok and len(rows) == sum(range(5 - given)) + 14 + 3 + 14 + 1 + 5
    assert rows[0][0].startswith("forward 0 (context ")
    assert rows[-6][0].endswith("root mean square for max")
    assert rows[-5][0].startswith("round, both blocks: the draw furthest")
    assert [r[1] for r in rows[-5:]] == [0.0] * 5
    assert [r[2] for r in rows[-4:-2]] == [7.0 - given, 1.0]


@lru_cache(maxsize=None)
def sound_run() -> tuple:
    """(params, prompt, the sound program's forwards of the check's two
    parts, the reference's rows along them), once for the five controls."""
    _, sound, params = make_engine()
    prompt = tokens(21, 38)
    held = serve_blocks.check_forwards(sound, params, prompt, ref)
    ctx = {"reference": ref, "config": dict(TOY)}
    return params, prompt, held, serve_blocks.reference_rows(
        ctx, params, sum(held, []))


@pytest.mark.parametrize("fault", ["in_block_causal", "commit_left_out",
                                   "rows_left_counted", "remainder_dropped",
                                   "chunk_causal"])
def test_a_fault_in_the_rounds_fails_the_check(fault):
    """The five controls the cell's ``correct`` must see, at toy size
    (benchmarks/tests/control_sdar.py reads them on the chip), along the
    blocks the sound program held."""
    from benchmarks.tests import control_sdar

    params, prompt, held, want = sound_run()
    assert worst_rel_err(control_sdar.rows_of(held), want) < 1e-3
    kept = (sdar_moe.kv_cache, InferenceEngine._commit,
            ContinuousBatcher._admit)
    with control_sdar.fault(fault):
        _, engine, _ = make_engine(fresh=True)  # traced under the fault
        faulty = serve_blocks.check_forwards(engine, params, prompt, ref,
                                             follow=held)
    assert worst_rel_err(control_sdar.rows_of(faulty), want) > 1e-2
    assert (sdar_moe.kv_cache, InferenceEngine._commit,
            ContinuousBatcher._admit) == kept  # taken away


def test_a_fault_in_the_round_program_alone_fails_the_check():
    """What no forward read alone can see: the round program's own tail. A
    round that packs a slot's run a place late streams other tokens than
    the checked forwards committed, and the check's last rows say so while
    every row of logits holds."""
    outputs = InferenceEngine._round_outputs

    def a_place_late(self, kind, params, out, lane):
        if kind == "blocks":
            out = {**out, "tokens": jnp.roll(out["tokens"], 1, axis=1)}
        return outputs(self, kind, params, out, lane)

    ctx = {"reference": ref, "config": dict(TOY, torch_dtype="float32")}
    InferenceEngine._round_outputs = a_place_late
    try:
        _, engine, params = make_engine(fresh=True)
        ok, rows = serve_blocks.logits_check(ctx, engine, params,
                                             tokens(21, 38))
    finally:
        InferenceEngine._round_outputs = outputs
    assert not ok and all(r[-1] for r in rows[:-5])
    assert not rows[-5][-1]  # the whole run is not what the forwards allow


def test_a_prefills_last_logits_are_the_block_causal_ones():
    """The one-shot program and the chunks each return the last position's
    logits; block-causally that position sees its whole block."""
    _, engine, params = make_engine()
    prompt = tokens(8, 36)
    want = ref.forward_logits(params, [prompt], TOY)[0]
    _, last = engine.prefill(params, prompt[:12])
    assert worst_rel_err([np.asarray(last)[0]], [ref.forward_logits(
        params, [prompt[:12]], TOY)[0, -1]]) < 1e-3
    _, last = engine.prefill_chunked(params, engine.init_cache(), prompt, 0)
    assert worst_rel_err([np.asarray(last)[0]], [want[-1]]) < 1e-3
    # a causal reading of the same rows is another model
    causal = ref.forward_logits(params, [prompt], {**TOY, "block_length": 1})
    assert worst_rel_err([causal[0, -2]], [want[-2]]) > 1e-2


def test_a_denoise_forward_leaves_the_lengths_and_a_commit_moves_them():
    _, engine, params = make_engine()
    cache, slot, given = serve_blocks.admitted(engine, params, tokens(9, 22))
    assert given == tokens(9, 22)[20:]
    block = np.full((3, 4), MASK, np.int32)
    live = np.arange(3) == slot
    cache, logits = engine.block_forward(params, cache, block, live)
    assert np.asarray(cache["lengths"]).tolist() == (20 * live).tolist()
    assert logits.shape == (3, 4, TOY["vocab_size"])
    cache, none = engine.block_forward(params, cache, block, live, True)
    assert none is None
    assert np.asarray(cache["lengths"]).tolist() == (24 * live).tolist()


# ---- (c) the confidence rule ------------------------------------------------


def test_transfer_counts_add_up_to_the_block():
    for bd in (1, 2, 4, 8, 16, 32):
        for steps in range(1, bd + 1):
            owed = [int(sampling.transfer_count(bd, steps, jnp.int32(s)))
                    for s in range(steps)]
            assert owed == ref.transfer_counts(bd, steps)
            assert sum(owed) == bd and max(owed) - min(owed) <= 1


@pytest.mark.parametrize("rule", sampling.REMASKING)
def test_the_unmask_rule_is_the_references(rule):
    rng = np.random.default_rng(4)
    model = {"remasking": rule, "confidence_threshold": 0.5}
    for trial in range(24):
        logits = rng.normal(0, 4.0, (8, 32)).astype(np.float32)
        masked = rng.random(8) < 0.6
        x0 = logits.argmax(-1)
        owed = int(rng.integers(1, 4))
        fix, passed = sampling.confidence_unmask(
            jnp.asarray(logits)[None], jnp.asarray(x0, jnp.int32)[None],
            jnp.asarray(masked)[None], owed, rule, 0.5)
        want = ref.unmask(logits, x0, masked, owed, model)
        assert np.asarray(fix)[0].tolist() == want.tolist(), trial
        assert not (np.asarray(fix)[0] & ~masked).any()
        p = np.exp(logits - logits.max(-1, keepdims=True))
        conf = (p / p.sum(-1, keepdims=True)).max(-1)
        assert int(passed[0]) == int((masked & (conf > 0.5)).sum())
    with pytest.raises(ValueError, match="unknown remasking"):
        sampling.confidence_unmask(jnp.zeros((1, 4, 8)), jnp.zeros(
            (1, 4), jnp.int32), jnp.ones((1, 4), bool), 1, "sequential", 0.9)


def test_confident_positions_leave_several_a_forward_and_are_counted():
    """A head drawn sixty times as wide makes every softmax a spike: the
    dynamic rule fixes a whole block in its first forward (a forward a
    block of 4 tokens where the floor takes 4), the counters say so, and the
    tokens are still the published loop's. Every block starts with a fused
    forward, three of the four are committed inside the next one's, and the
    stream's last block is owed nothing."""
    _, engine, params = make_engine(fresh=True)
    sharp = {**params, "lm_head": params["lm_head"] * 60.0}
    prompt = tokens(11, 32)
    want = ref.generate(sharp, prompt, 16, TOY)
    out = ContinuousBatcher(engine, sharp, seed=0).run(
        [Request(uid="a", prompt=prompt, max_new_tokens=16)])
    assert out["a"].tokens == want
    text = engine.obs.registry.prometheus()
    got = {line.split(" ")[0]: float(line.split(" ")[1])
           for line in text.splitlines()
           if line.startswith("picotron_diffusion")}
    denoise = got['picotron_diffusion_forwards_total{kind="denoise"}']
    fused = got['picotron_diffusion_forwards_total{kind="fused"}']
    assert got['picotron_diffusion_forwards_total{kind="commit"}'] == 0
    assert got["picotron_diffusion_blocks_total"] == 3 and fused == 4
    assert got["picotron_diffusion_positions_unmasked_total"] == 16
    assert got["picotron_diffusion_threshold_passes_total"] >= 12
    assert denoise < 12 and 16 / (denoise + fused) > 1.0
    assert got["picotron_diffusion_rows_total"] == 4 * (denoise + fused)


def test_the_batcher_puts_the_counters_on_metrics():
    """On seeded weights no confidence passes 0.9: a block of 4 takes four
    denoise forwards, the first of them fused with the commit of the block
    before it, so a round of two blocks counts eight forwards, two of them
    ``kind="fused"``, none ``kind="commit"``: a token a forward; the expert
    share's counters count live rows alone."""
    _, engine, params = make_engine(fresh=True)
    prompts = [tokens(12, 32), tokens(13, 16)]
    ContinuousBatcher(engine, params, seed=0).run(
        [Request(uid=str(i), prompt=p, max_new_tokens=8)
         for i, p in enumerate(prompts)])
    text = engine.obs.registry.prometheus()
    value = lambda name: float(next(
        line for line in text.splitlines()
        if line.startswith(name + " ")).split(" ")[1])
    assert value('picotron_diffusion_forwards_total{kind="denoise"}') == 6
    assert value('picotron_diffusion_forwards_total{kind="fused"}') == 2
    assert value('picotron_diffusion_forwards_total{kind="commit"}') == 0
    # each slot's first block, inside its second's first forward; the second
    # ended the stream
    assert value("picotron_diffusion_blocks_total") == 2
    assert value("picotron_diffusion_positions_unmasked_total") == 16
    assert value("picotron_diffusion_threshold_passes_total") == 0
    assert value("picotron_diffusion_rows_total") == 8 * 2 * 4
    # two layers: 48 prompt rows (two chunks and a bucket) and 72 forwarded
    # ones (64 that can gain a token, 8 committed), four experts a token
    assert value("picotron_moe_assignments_total") <= 2 * 120 * 4
    assert value("picotron_moe_layer_steps_total") == 2 * (3 + 8)
    assert value('picotron_dispatch_total{kind="blocks"}') == 1


# ---- (d) the share, the configuration, the counts ---------------------------


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """Ranks 0-3 of 4, four experts each, and no shared expert to count
    once, against the uncut layer of sixteen, and against the
    reference's."""
    uncut_model = dict(num_experts=16, ep_size=1, num_local_experts=16)
    uncut = make_config(uncut_model).model
    stack = jax.jit(lambda k: sdar_moe.init_params(k, uncut))(
        jax.random.PRNGKey(5))["layers"]
    lp = {n: v[0] for n, v in stack.items()}
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 12, 64), jnp.float32)
    live = jnp.ones((2, 12), bool)

    def mlp(lp, x, m, live):
        return jax.jit(lambda lp, x, live: sdar_moe.expert_mlp(
            lp, x, m, live))(lp, x, live)

    whole, (assigned, hit, *_) = mlp(lp, x, uncut, live)
    assert int(assigned) == 2 * 12 * 4 and int(hit) <= 16
    total, held = jnp.zeros_like(whole), 0
    for rank in range(4):
        m = make_config(dict(ep_rank=rank)).model
        part = {**lp, **{n: lp[n][4 * rank:4 * rank + 4]
                         for n in ("w1", "w3", "w2")}}
        y, (n, *_) = mlp(part, x, m, live)
        total, held = total + y, held + int(n)
    np.testing.assert_allclose(total, whole, atol=2e-5)
    assert held == 2 * 12 * 4  # every token's experts are held by some rank
    want = ref.experts(lp, x.reshape(24, 64), {**TOY, **uncut_model},
                       "highest")
    np.testing.assert_allclose(whole.reshape(24, 64), want, atol=2e-5)
    y, (n, *_) = mlp(lp, x, uncut, jnp.zeros((2, 12), bool))
    assert not np.asarray(y).any() and int(n) == 0


def published_config() -> dict:
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def published_model() -> ModelConfig:
    from benchmarks import common

    fields = {f.name for f in ModelConfig.__dataclass_fields__.values()}
    section = common.model_section(published_config())
    return ModelConfig(**{k: v for k, v in section.items() if k in fields})


def test_the_configuration_the_counts_and_the_tree_agree():
    from benchmarks import opcount_sdar as op

    pub, m = published_config(), published_model()
    assert (m.hidden_size, m.num_attention_heads, m.num_key_value_heads,
            m.head_dim, m.moe_intermediate_size) == (2048, 32, 4, 128, 768)
    assert (m.num_experts, m.ep_size, m.num_experts_per_tok) == (16, 8, 8)
    assert sdar_moe.router_width(m) == 128 and m.rope_theta == 1e6
    assert (m.block_length, m.denoising_steps, m.remasking,
            m.confidence_threshold, m.mask_token_id) == (
                4, 4, "low_confidence_dynamic", 0.9, m.vocab_size - 1)
    n = sdar_moe.num_params(m)
    assert n == op.num_params(pub) == 1_213_453_312
    assert f"{n:,}" in pub["deployment"]
    slots, window = pub["serve"]["slots"], pub["serve"]["max_seq_len"]
    cache = jax.eval_shape(lambda: sdar_moe.init_cache(m, slots, window))
    assert cache["k"].shape == (12, 32, 12288, 4, 128)
    assert kv_cache.cache_bytes(cache) - 4 * slots \
        == op.cache_bytes(pub, slots, window) == 9_663_676_416
    assert op.kv_bytes_per_row(pub) * 12 == 24_576
    assert pub["reduced_from"] == {"num_hidden_layers": 48,
                                   "num_experts": 128,
                                   "vocab_size": 151936, "ep_size": 1}
    assert pub["vocab_size"] * 8 == 151936
    for rule in ("max_seq_len", "prefill_chunk", "decode_block_len"):
        assert {"max_seq_len": window, "prefill_chunk": 512,
                "decode_block_len": 8}[rule] % m.block_length == 0


def test_seeded_draws_are_keyes_at_the_shared_leaves():
    """One drawing function for both blocks (``normed_gqa_moe.draw_tree``):
    norm weights ones, ``wo`` four times as wide, ``w2`` half."""
    _, _, params = make_engine()
    layers = params["layers"]
    for name, gain in (("wq", 1.0), ("wo", 4.0), ("w2", 0.5),
                       ("router", 1.0)):
        w = np.asarray(layers[name], np.float32)
        bound = gain * (1.0 / w.shape[-2]) ** 0.5
        assert 0.9 * bound < np.abs(w).max() <= bound, name
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        assert (np.asarray(layers[name]) == 1).all()
    assert set(layers) == {"attn_norm", "mlp_norm", "q_norm", "k_norm", "wq",
                           "wk", "wv", "wo", "router", "w1", "w3", "w2"}


@pytest.mark.parametrize("model,sections,says", [
    (dict(block_length=3), {}, "block_length 3 is not one of"),
    (dict(denoising_steps=5), {}, "denoising_steps 5 outside [1, "),
    (dict(denoising_steps=0), {}, "denoising_steps 0 outside [1, "),
    (dict(remasking="sequential"), {}, "remasking 'sequential'"),
    (dict(mask_token_id=256), {}, "mask_token_id 256 outside"),
    ({}, {"inference": {"prefill_chunk": 30}}, "must be multiples of"),
    ({}, {"inference": {"decode_block_len": 6}}, "must be multiples of"),
    (dict(rope_scaling={"type": "yarn"}), {}, "rotates by the plain table"),
    (dict(num_local_experts=12), {}, "is not the router's width 16"),
    (dict(mlp_only_layers=[0]), {}, "mlp_only_layers = [] only"),
    (dict(norm_topk_prob=False), {}, "norm_topk_prob = True only"),
])
def test_validate_refuses_what_the_block_lacks(model, sections, says):
    with pytest.raises(ValueError) as e:
        make_config(model, **sections)
    assert str(e.value).startswith("model_type 'sdar_moe'")
    assert says in str(e.value)


def test_the_engine_holds_its_window_to_whole_blocks():
    with pytest.raises(ValueError, match="multiple of block_length"):
        InferenceEngine(make_config(), slots=2, max_seq_len=126)
    _, engine, params = make_engine()
    assert engine.blocks and engine.store is None
    with pytest.raises(ValueError, match="tokens and waiting \\[slots, block_length\\]"):
        engine.decode_block(params, engine.init_cache(), np.zeros(3),
                            np.zeros((8, 2), np.uint32), *[np.zeros(3)] * 5,
                            given=np.zeros(3))


# ---- (e) the band: a fresh row sees to the end of its own block -------------


def dense_rule(q, k, v, lengths, scale, block):
    """The rule as it is written, in numpy float64."""
    B, S, nh, D = q.shape
    g = nh // k.shape[2]
    out = np.zeros((B, S, nh, D))
    for b in range(B):
        for s in range(S):
            pos = int(lengths[b]) - S + s
            last = min(pos // block * block + block - 1, int(lengths[b]) - 1)
            for h in range(nh):
                z = k[b, :last + 1, h // g].astype(np.float64) \
                    @ q[b, s, h].astype(np.float64) * scale
                p = np.exp(z - z.max())
                out[b, s, h] = (p / p.sum()) @ v[b, :last + 1, h // g]
    return out


def band_case(B, S, T, seed=0, nh=8, nkv=2, D=16):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=s).astype(np.float32) for s in (
        (B, S, nh, D), (B, T, nkv, D), (B, T, nkv, D)))
    return q, k, v


@pytest.mark.parametrize("B,S,lengths", [
    (3, 1, [5, 17, 32]),   # a decode step
    (3, 4, [8, 20, 32]),   # a verify's rows, or a block's
    (1, 16, [32]),         # a prefill chunk
])
def test_block_1_is_the_causal_band_bit_for_bit(B, S, lengths):
    """``decode_attention(..., block=1)`` against the lines it had before it
    took the parameter, on the dense block's three shapes of call."""
    q, k, v = band_case(B, S, 32)
    L = jnp.asarray(lengths, jnp.int32)
    got = kv_cache.decode_attention(*map(jnp.asarray, (q, k, v)), L, 0.25)
    assert (np.asarray(got) == np.asarray(kv_cache.decode_attention(
        *map(jnp.asarray, (q, k, v)), L, 0.25, 1))).all()
    qg = jnp.asarray(q).reshape(B, S, 2, 4, 16)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, jnp.asarray(k),
                        preferred_element_type=jnp.float32) * 0.25
    pos_q = L[:, None] - S + jnp.arange(S)[None, :]
    mask = jnp.arange(32)[None, None, :] <= pos_q[:, :, None]
    scores = jnp.where(mask[:, None, None, :, :], scores, kv_cache.NEG_INF)
    p = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    was = jnp.einsum("bkgst,btkd->bskgd", p,
                     jnp.asarray(v).astype(jnp.float32)).reshape(B, S, 8, 16)
    assert (np.asarray(got) == np.asarray(was)).all()


@pytest.mark.parametrize("block", [1, 4, 8])
@pytest.mark.parametrize("B,S,lengths", [
    (2, 8, [16, 32]),  # every slot's rows, aligned (S == 8: one block)
    (1, 16, [24]),     # one slot's chunk of whole blocks
])
def test_the_band_is_the_rule(block, B, S, lengths):
    q, k, v = band_case(B, S, 32, seed=block)
    L = jnp.asarray(lengths, jnp.int32)
    want = dense_rule(q, k, v, lengths, 0.25, block)
    dense = kv_cache.decode_attention(*map(jnp.asarray, (q, k, v)), L, 0.25,
                                      block)
    np.testing.assert_allclose(np.asarray(dense), want, atol=2e-5)


def test_the_sliced_kernel_is_not_asked_for_a_band():
    """A chunk of whole blocks (not the plain decode shape) under a forced
    ``flash``: the sliced kernel sees a causal band and says so."""
    q, k, v = (jnp.asarray(a, jnp.bfloat16)
               for a in band_case(1, 16, 32, D=128))
    cache = {"k": k[None], "v": v[None]}
    L = jnp.asarray([24], jnp.int32)
    with pytest.raises(NotImplementedError, match="causal band"):
        kv_cache.attend(q, cache, L, 0.25, 0, impl="flash", block=4)
    kv_cache.attend(q, cache, L, 0.25, 0, impl="dense", block=4)


@pytest.mark.parametrize("heads_a_row", [1, 2])
def test_a_whole_block_rides_beside_the_heads_of_the_stacked_kernel(
        heads_a_row):
    """``attend(impl="flash", block=S)``: a slot's S rows, one aligned
    block, through ``flash_decode_stacked`` with S times the query heads
    (what a denoise forward runs on the chip), against the rule; K/V a head
    a row of whole lanes, and two heads packed to a row."""
    D = 128 // heads_a_row
    q, k, v = band_case(2, 4, 64, seed=3, nh=8, nkv=2, D=D)
    lengths = [12, 64]
    want = dense_rule(q, k, v, lengths, 0.25, 4)
    bf = lambda a: jnp.asarray(a, jnp.bfloat16)
    leaf = lambda a: jnp.stack([jnp.zeros_like(bf(a)), bf(a)]).reshape(
        2, 2, 64, 2 // heads_a_row, 128)
    cache = {"k": leaf(k), "v": leaf(v)}
    assert kv_cache.plain_decode(bf(q), cache, 4)
    assert not kv_cache.plain_decode(bf(q), cache)
    got = kv_cache.attend(bf(q), cache, jnp.asarray(lengths, jnp.int32),
                          0.25, 1, impl="flash", block=4)
    dense = kv_cache.attend(bf(q), cache, jnp.asarray(lengths, jnp.int32),
                            0.25, 1, impl="dense", block=4)
    scale = np.abs(want).max()
    assert np.abs(np.asarray(got, np.float32) - want).max() < 0.02 * scale
    assert np.abs(np.asarray(dense, np.float32) - want).max() < 0.02 * scale
