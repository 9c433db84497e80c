"""Pallas kernels vs their XLA oracles, via the TPU interpreter on CPU.

The reference validates its fast paths against pure-torch formulations
(LlamaRMSNorm vs TritonRMSNorm, SDPA vs flash-attn — model.py:147-157,191);
here the Pallas flash-attention and RMSNorm kernels are checked against
ops.attention.sdpa and ops.rmsnorm.rms_norm in interpret mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

if not hasattr(pltpu, "force_tpu_interpret_mode"):
    # environment, not code: the installed jax predates the Mosaic
    # interpret-mode context manager every test here runs under — skip
    # (pass/skip signal) instead of failing on an AttributeError floor
    pytest.skip(
        f"jax {jax.__version__} lacks pltpu.force_tpu_interpret_mode "
        "(the TPU-interpreter-on-CPU API this module needs)",
        allow_module_level=True)

from picotron_tpu.ops.attention import sdpa
from picotron_tpu.ops.pallas.flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    _pick_block,
    _scale_folds,
    causal_kv_blocks,
    flash_attention,
    flash_attention_with_lse,
    flash_block_grads,
)
from picotron_tpu.ops.pallas.rmsnorm import rms_norm_pallas
from picotron_tpu.ops.rmsnorm import rms_norm


def _qkv(b=2, s=256, h=2, d=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (b, s, h, d)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


# merged requires head_dim % 128 == 0, so its cases run at d=128; paired (the
# training stack's form of the default at heads of 64) takes two heads of 64
LAYOUT_D = [("folded", 64), ("bshd", 64), ("merged", 128), ("paired", 64)]


@pytest.mark.parametrize("layout,d", LAYOUT_D)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_sdpa(causal, layout, d):
    q, k, v = _qkv(d=d)
    scale = 0.125
    with pltpu.force_tpu_interpret_mode():
        got = flash_attention(q, k, v, scale, causal=causal, block_q=128,
                              block_k=128, layout=layout)
    want = sdpa(q, k, v, scale, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout,d", LAYOUT_D)
def test_flash_lse_matches_block_attention(layout, d):
    from picotron_tpu.ops.attention import _causal_mask, block_attention

    q, k, v = _qkv(s=128, d=d)
    scale = 0.125
    with pltpu.force_tpu_interpret_mode():
        out, lse = flash_attention_with_lse(q, k, v, scale, causal=True,
                                            block_q=128, block_k=128,
                                            layout=layout)
    mask = _causal_mask(q.shape[1], k.shape[1], 0)
    want_out, want_lse = block_attention(q, k, v, scale, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout,d", LAYOUT_D)
def test_flash_grads_match_sdpa(layout, d):
    q, k, v = _qkv(s=128, d=d)
    scale = 0.125

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, scale, causal=True, block_q=64,
                              block_k=64, layout=layout)
        return jnp.sum(out * jnp.cos(out))

    def loss_ref(q, k, v):
        out = sdpa(q, k, v, scale, causal=True)
        return jnp.sum(out * jnp.cos(out))

    with pltpu.force_tpu_interpret_mode():
        g_got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(g_got, g_want, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-5, atol=5e-5, err_msg=f"d{name}")


def _tiles_walked(seq, bq, bk):
    """Tile pairs a head's causal walk visits (every one masked; the rest
    lie wholly above the diagonal), from the bound the kernels loop to."""
    return sum(int(causal_kv_blocks(seq // bk, (qi + 1) * bq - 1, bk))
               for qi in range(seq // bq))


# Several tiles a row and a column (S 512 with blocks of 128), the diagonal
# crossing two tiles of a row (block_q != block_k, both ways), the scale
# folded into an operand (1/8 at D 64) and kept on the scores (D 128), in
# float32 (the tolerances of the tests above) and in bf16 (the kernels'
# training dtype: against the float32 oracle on the same rounded inputs,
# relative to its largest entry).
TILINGS = [(128, 128), (256, 128), (128, 256)]
TILED_LAYOUT_D = [("folded", 64), ("folded", 128), ("merged", 128),
                  ("paired", 64)]


def _close(got, want, dtype, f32_tol, name=""):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=f32_tol, atol=f32_tol,
                                   err_msg=name)
    else:
        tol = 2e-2 * max(float(np.max(np.abs(want))), 1.0)
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=tol,
                                   err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout,d", TILED_LAYOUT_D)
@pytest.mark.parametrize("bq,bk", TILINGS)
def test_flash_tiled_matches_oracle(bq, bk, layout, d, dtype):
    """Forward, lse and all three gradients over a multi-tile causal walk."""
    from picotron_tpu.ops.attention import _causal_mask, block_attention

    q, k, v = (x.astype(dtype) for x in _qkv(b=1, s=512, d=d, seed=11))
    scale = d ** -0.5
    assert _scale_folds(scale) == (d == 64)
    assert _tiles_walked(512, bq, bk) > 512 // bq  # several tiles a row

    def loss(attend):
        def f(q, k, v):
            out = attend(q, k, v).astype(jnp.float32)
            return jnp.sum(out * jnp.cos(out))
        return f

    flash = lambda q, k, v: flash_attention(
        q, k, v, scale, causal=True, block_q=bq, block_k=bk, layout=layout)
    with pltpu.force_tpu_interpret_mode():
        out, lse = flash_attention_with_lse(
            q, k, v, scale, causal=True, block_q=bq, block_k=bk,
            layout=layout)
        grads = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    want_out, want_lse = block_attention(q32, k32, v32, scale,
                                         _causal_mask(512, 512, 0))
    want = jax.grad(loss(lambda q, k, v: sdpa(q, k, v, scale, causal=True)),
                    argnums=(0, 1, 2))(q32, k32, v32)
    _close(out, want_out, dtype, 2e-5, "out")
    _close(lse, want_lse, dtype, 2e-5, "lse")
    for g, w, name in zip(grads, want, "qkv"):
        _close(g, w, dtype, 5e-5, f"d{name}")


@pytest.mark.parametrize("layout,d", [("folded", 64), ("merged", 128)])
@pytest.mark.parametrize("sq,sk", [(256, 512), (512, 256)])
def test_flash_rect_block_matches_einsum(sq, sk, layout, d):
    """The ring's entry points on a full-attend half block, Sq != Sk: the
    forward with its lse, and the block gradients from that out/lse."""
    from picotron_tpu.ops.attention import block_attention

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q, do = (jax.random.normal(kk, (1, sq, 2, d)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (1, sk, 2, d)) for kk in ks[2:])
    scale = d ** -0.5
    kw = dict(causal=False, block_q=128, block_k=128, layout=layout)
    with pltpu.force_tpu_interpret_mode():
        out, lse = flash_attention_with_lse(q, k, v, scale, **kw)
        grads = flash_block_grads(q, k, v, out, lse, do, scale, **kw)
    want_out, want_lse = block_attention(q, k, v, scale, mask=None)
    want = jax.grad(lambda q, k, v: jnp.sum(
        block_attention(q, k, v, scale, mask=None)[0] * do),
        argnums=(0, 1, 2))(q, k, v)
    _close(out, want_out, jnp.float32, 2e-5, "out")
    _close(lse, want_lse, jnp.float32, 2e-5, "lse")
    for g, w, name in zip(grads, want, "qkv"):
        _close(g, w, jnp.float32, 5e-5, f"d{name}")


@pytest.mark.parametrize("b,s,h", [(1, 256, 2), (2, 512, 4), (3, 1024, 6)])
def test_flash_paired_matches_folded(b, s, h):
    """Heads of 64 two to a lane row against a head a row, same inputs,
    causal, the kernels' own tiles: forward, lse, dQ, dK, dV."""
    q, k, v = _qkv(b=b, s=s, h=h, d=64, seed=b)
    do = jax.random.normal(jax.random.PRNGKey(9), q.shape)
    got = {}
    with pltpu.force_tpu_interpret_mode():
        for layout in ("folded", "paired"):
            out, lse = flash_attention_with_lse(q, k, v, 0.125, layout=layout)
            grads = jax.vjp(lambda q, k, v: flash_attention(
                q, k, v, 0.125, layout=layout), q, k, v)[1](do)
            got[layout] = (out, lse, *grads)
    for a, w, name in zip(got["paired"], got["folded"],
                          ("out", "lse", "dq", "dk", "dv")):
        _close(a, w, jnp.float32, 5e-5 if name[0] == "d" else 2e-5, name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rope_rows_matches_apply_rope(dtype):
    """RoPE on [B, S, H * D] rows (q and k in one call) against
    ``apply_rope`` through the [.., H, D] view: values and gradients."""
    from picotron_tpu.ops.pallas.rope import rope_rows
    from picotron_tpu.ops.rope import apply_rope, precompute_rope

    B, S, H, D = 2, 64, 6, 64  # 384 lanes a row: three blocks of 128
    cos, sin = precompute_rope(S, D, 10000.0, dtype)
    q, k, _ = (x.astype(dtype) for x in _qkv(b=B, s=S, h=H, d=D, seed=4))
    flat = lambda x: x.reshape(B, S, H * D)
    mix = lambda a, b: jnp.sum(jnp.sin(a.astype(jnp.float32))
                               + jnp.cos(b.astype(jnp.float32)))
    with pltpu.force_tpu_interpret_mode():
        got = rope_rows(flat(q), flat(k), cos, sin)
        g_got = jax.grad(lambda q, k: mix(*rope_rows(q, k, cos, sin)),
                         argnums=(0, 1))(flat(q), flat(k))
    want = (apply_rope(q, cos, sin), apply_rope(k, cos, sin))
    g_want = jax.grad(lambda q, k: mix(apply_rope(q, cos, sin),
                                       apply_rope(k, cos, sin)),
                      argnums=(0, 1))(q, k)
    for a, w, name in zip(got + g_got, want + g_want, ("q", "k", "dq", "dk")):
        _close(a, flat(w), dtype, 2e-6, name)


@pytest.mark.parametrize("seq,d,bq,bk,tiles,folded", [
    (2048, 64, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, 10, True),    # train-2k
    (4096, 128, DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K, 36, False),  # train-pp2tp2
    (512, 64, 256, 128, 6, True),   # of 8: two skipped in the first row
    (512, 64, 128, 256, 6, True),
    (512, 80, 128, 128, 10, False),
])
def test_causal_walk_and_scale_fold(seq, d, bq, bk, tiles, folded):
    bq, bk = _pick_block(seq, bq), _pick_block(seq, bk)
    assert _tiles_walked(seq, bq, bk) == tiles
    assert _scale_folds(d ** -0.5) == folded


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_reference(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 96, 128)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(1), (128,)).astype(dtype)
    with pltpu.force_tpu_interpret_mode():
        got = rms_norm_pallas(x, w, 1e-5)
    want = rms_norm(x, w, 1e-5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=1e-2 if
                               dtype == jnp.bfloat16 else 1e-6, atol=1e-2 if
                               dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("rows", [512, 20, 100, 1028])
def test_rmsnorm_grads_match_reference(rows):
    """512 rows: whole blocks. 20: one block, the whole array. 100 and 1,028
    under a block of 1,024: rows no block of 8 or more divides, made up to
    whole blocks with zero rows."""
    x = jax.random.normal(jax.random.PRNGKey(0), (rows, 128))
    w = jax.random.normal(jax.random.PRNGKey(1), (128,)) + 1.0

    def loss_pallas(x, w):
        return jnp.sum(jnp.sin(rms_norm_pallas(x, w, 1e-5)))

    def loss_ref(x, w):
        return jnp.sum(jnp.sin(rms_norm(x, w, 1e-5)))

    with pltpu.force_tpu_interpret_mode():
        gx, gw = jax.grad(loss_pallas, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(rw), rtol=5e-5, atol=5e-5)


def test_merged_block_grads_match_einsum():
    """The ring-attention building block in the merged layout: block
    backward fed an external out/lse must match AD through the einsum
    block (full-attend block, the ring's off-diagonal case)."""
    from picotron_tpu.ops.attention import block_attention
    from picotron_tpu.ops.pallas.flash_attention import (
        flash_attention_with_lse, flash_block_grads)

    q, k, v = _qkv(s=128, d=128, seed=7)
    scale = 0.125
    with pltpu.force_tpu_interpret_mode():
        out, lse = flash_attention_with_lse(q, k, v, scale, causal=False,
                                            block_q=64, block_k=64,
                                            layout="merged")
    do = jax.random.normal(jax.random.PRNGKey(8), out.shape)
    with pltpu.force_tpu_interpret_mode():
        dq, dk, dv = flash_block_grads(q, k, v, out, lse, do, scale,
                                       causal=False, block_q=64, block_k=64,
                                       layout="merged")

    def ref_f(q, k, v):
        o, _ = block_attention(q, k, v, scale, mask=None)
        return jnp.sum(o * do)

    rq, rk, rv = jax.grad(ref_f, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip((dq, dk, dv), (rq, rk, rv), "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5, err_msg=f"d{name}")


def test_merged_layout_rejects_unaligned_head_dim():
    q, k, v = _qkv(d=64)
    with pytest.raises(ValueError, match="head_dim % 128"):
        flash_attention(q, k, v, 0.125, layout="merged")


def test_flash_blocks_configurable_through_model(tiny_model_kwargs):
    """model.flash_block_q/k and flash_layout reach the kernel through
    _attention: a custom tiling or the bshd layout must not change the
    math."""
    from picotron_tpu.config import Config
    from picotron_tpu.models.llama import _attention

    def cfg_with(bq, bk, layout="folded"):
        return Config.from_dict({
            "distributed": {"use_cpu": True},
            "model": dict(tiny_model_kwargs, attention_impl="flash",
                          flash_block_q=bq, flash_block_k=bk,
                          flash_layout=layout),
            "training": {"seq_length": 128},
            "dataset": {"name": "synthetic"},
        })

    q, k, v = _qkv(b=1, s=128, h=2, d=64, seed=3)
    with pltpu.force_tpu_interpret_mode():
        got = _attention(q, k, v, cfg_with(32, 128))
        ref = _attention(q, k, v, cfg_with(None, None))
        bshd = _attention(q, k, v, cfg_with(None, None, layout="bshd"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(bshd), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------- #
# the training stack's rule: heads of 64 two to a lane row
# --------------------------------------------------------------------------- #

SMOL = dict(num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
            hidden_size=256, intermediate_size=512, vocab_size=256,
            max_position_embeddings=128, rope_theta=10000.0, dtype="float32",
            attention_impl="flash")


def _cfg(model=None, remat="none", **distributed):
    from picotron_tpu.config import Config

    return Config.from_dict({
        "distributed": {"use_cpu": True, **distributed},
        "model": {**SMOL, **(model or {})},
        "training": {"seq_length": 128, "remat": remat},
        "dataset": {"name": "synthetic"}})


@pytest.fixture
def layouts_called(monkeypatch):
    """The ``layout`` of every ``flash_attention`` call the model makes."""
    from picotron_tpu.ops.pallas import flash_attention as fa

    seen, real = [], fa.flash_attention

    def spy(*a, **kw):
        seen.append(kw.get("layout", "folded"))
        return real(*a, **kw)

    monkeypatch.setattr(fa, "flash_attention", spy)
    return seen


@pytest.fixture
def plain_interpreter(monkeypatch):
    """The kernels through Pallas' plain interpreter: the TPU one simulates
    memory with ordered callbacks, which ``jax.checkpoint`` refuses."""
    from functools import partial

    from jax.experimental import pallas as pl

    monkeypatch.setattr(pl, "pallas_call",
                        partial(pl.pallas_call, interpret=True))


def _stack(cfg, h, return_kv=False):
    """(loss, gradients) of the cfg's layer stack over ``h``, or one
    layer's prefill outputs, on one device."""
    from picotron_tpu.models import llama
    from picotron_tpu.topology import build_topology
    from picotron_tpu.utils import shard_map

    from jax.sharding import PartitionSpec as P

    params = llama.init_params(jax.random.PRNGKey(0), cfg.model)["layers"]
    specs = llama.param_pspecs(cfg.model)["layers"]
    cos, sin = llama.rope_tables(cfg)
    cos, sin = cos[:h.shape[1]], sin[:h.shape[1]]
    mesh = build_topology(1, 1, 1, 1, devices=jax.devices()[:1]).mesh

    def loss(params, h):
        out = llama.layers_forward(params, h, cos, sin, cfg)
        return jnp.sum(out * jnp.cos(out))

    def prefill(params, h):
        lp = jax.tree.map(lambda a: a[0], params)
        out, (k, v) = llama.decoder_layer(lp, h, cos, sin, cfg,
                                          return_kv=True)
        return out, k, v

    fn = prefill if return_kv else jax.value_and_grad(loss, argnums=(0, 1))
    out_specs = (P(),) * 3 if return_kv else (P(), (specs, P()))
    return jax.jit(shard_map(fn, mesh, in_specs=(specs, P()),
                             out_specs=out_specs))(params, h)


@pytest.mark.parametrize("remat", ["full", "save_attn"])
def test_paired_stack_matches_folded_stack(remat, layouts_called,
                                           plain_interpreter, monkeypatch):
    """The layer stack through the paired kernels and RoPE on rows against
    the same stack a head a row: loss and every gradient."""
    from picotron_tpu.models import llama

    cfg = _cfg(remat=remat)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 256))
    assert llama.flash_heads_per_row(cfg) == 2
    got = _stack(cfg, h)
    assert set(layouts_called) == {"paired"}
    del layouts_called[:]
    monkeypatch.setattr(llama, "flash_heads_per_row", lambda cfg: 1)
    want = _stack(cfg, h)
    assert set(layouts_called) == {"folded"}
    for a, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(a, w, jnp.float32, 5e-5)


# every way out of the rule: (model overrides, distributed, the gauge's value)
WAYS_OUT = {
    "odd_heads": (dict(num_attention_heads=3, num_key_value_heads=3,
                       hidden_size=192), {}),
    "heads_of_128": (dict(num_attention_heads=2, num_key_value_heads=2), {}),
    "odd_heads_a_rank": (dict(num_attention_heads=6, num_key_value_heads=6,
                              hidden_size=384), dict(tp_size=2)),
    "context_parallel": ({}, dict(cp_size=2)),
    "layout_by_the_user": (dict(flash_layout="bshd"), {}),
    "sdpa": (dict(attention_impl="sdpa"), {}),
}


@pytest.mark.parametrize("way", sorted(WAYS_OUT))
def test_paired_rule_ways_out(way, layouts_called, plain_interpreter):
    """Outside the rule the gauge reads 1 and the layer takes today's path
    (one device can run the stack wherever the mesh is one device)."""
    from picotron_tpu.models import llama

    model, distributed = WAYS_OUT[way]
    cfg = _cfg(model=model, **distributed)
    assert llama.flash_heads_per_row(cfg) == 1
    if distributed or way == "sdpa":
        return
    h = jax.random.normal(jax.random.PRNGKey(2),
                          (1, 128, cfg.model.hidden_size))
    _stack(cfg, h)
    assert layouts_called and set(layouts_called) == {
        cfg.model.flash_layout}


def test_prefill_keeps_a_head_a_row(layouts_called, plain_interpreter):
    """``return_kv=True`` (the engine's one-shot prefill) keeps the folded
    call whatever the gauge reads, and returns the K/V it always did."""
    from picotron_tpu.models import llama

    cfg = _cfg()
    assert llama.flash_heads_per_row(cfg) == 2
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 256))
    out, k, v = _stack(cfg, h, return_kv=True)
    assert layouts_called == ["folded"]
    assert k.shape == v.shape == (1, 128, 4, 64)


@pytest.mark.parametrize("shape,want", [
    # SmolLM-1.7B: 32 heads of 64; Mistral-7B-v0.3: 32 heads of 128
    (dict(hidden_size=2048, num_attention_heads=32, num_key_value_heads=32,
          intermediate_size=8192), 2),
    (dict(hidden_size=4096, num_attention_heads=32, num_key_value_heads=8,
          intermediate_size=14336), 1),
])
def test_flash_heads_per_row_gauge(shape, want):
    """``picotron_flash_heads_per_row`` as ``train.py`` sets it, from the
    one function the call site's rule uses."""
    from picotron_tpu.models import llama

    assert llama.flash_heads_per_row(_cfg(model=shape)) == want
