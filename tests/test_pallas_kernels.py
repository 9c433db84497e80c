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


# merged requires head_dim % 128 == 0, so its cases run at d=128
LAYOUT_D = [("folded", 64), ("bshd", 64), ("merged", 128)]


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
TILED_LAYOUT_D = [("folded", 64), ("folded", 128), ("merged", 128)]


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


def test_rmsnorm_grads_match_reference():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64, 128))
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
