"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

TPU-native equivalent of the reference's flash-attn CUDA dependency
(reference picotron/model.py:7,32-36,151-153; pinned flash-attn==2.5.0).
Same asymptotics as FlashAttention-2: O(S) memory (never materializes the
[S, S] score matrix in HBM), online softmax in fp32, log-sum-exp saved for
the backward, which re-derives P per block.

Three data layouts share the same kernel bodies (``model.flash_layout``):

- "folded" (default, battle-tested): the model's [B, S, H, D] is folded to
  [B*H, S, D] around the pallas_call; the grid walks (batch*head, q-block).
  The fold is a host-side transpose+reshape copy of every operand per call.
- "bshd" (interpret-mode only — REJECTED on hardware): the kernels consume
  [B, S, H, D] directly — grid (batch, head, q-block), the head dimension
  squeezed out by a size-None BlockSpec entry — avoiding the fold's
  transpose copies. On a v5e chip Mosaic refuses to
  lower it — the last two block dims must be (8k, 128m) or span the whole
  axis, and in [B, S, H, D] the head axis is second-to-last, so a
  squeezed head block is structurally un-lowerable regardless of D. The
  only hardware paths are (a) this folded layout or (b) the "merged"
  layout below. "folded" stays the production default; bshd remains as
  the interpret-mode record of the experiment.
- "merged" (head_dim % 128 == 0 geometries, e.g. Llama-2-7B's D=128): the
  [B, S, H, D] operands are viewed as [B, S, H*D] — a free reshape, minor
  dims merge — and the head grid axis selects a D-wide LANE-aligned slice
  of the last dim, which Mosaic accepts. Same zero-transpose-copy win the
  bshd experiment wanted, within the tiling rules.

K/V for one head live whole in VMEM (S*D*2B ~ 1 MB at S=8192, D=64)
while scores exist only as a [block_q, block_k] VMEM tile — the MXU sees
(block_q x D) @ (D x block_k) and (block_q x block_k) @ (block_k x D)
matmuls, all 128-aligned. The per-row LSE is materialized with a broadcast
128-lane minor dim ([BH, S, 128] / [B, S, H, 128]) — Mosaic requires the
last two block dims be (8k, 128m), so a lane-less layout can't be tiled
per-q-block (the in-tree TPU flash kernel uses the same trick).

Causality is handled at two levels: whole key-blocks strictly above the
diagonal are skipped (the fori_loop upper bound), the diagonal block gets an
iota mask. The softmax-backward row term delta = rowsum(dO * O) is computed
in-kernel from the O/dO blocks. GQA repetition happens in the model before
the call (as the reference repeats before its kernel, model.py:141-142).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

NEG_INF = -1e30
LANE = 128  # minor-dim width for the broadcast LSE layout

# 512 measured ~1.6x faster than 256 on v5e at S=2048, D=64 (the QK^T and
# PV matmuls are contraction/width-limited by D=64, so bigger tiles amortize
# better); VMEM still fits the fp32 [bq, bk] score tile comfortably.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _pick_block(seq: int, want: int) -> int:
    b = min(want, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


def causal_kv_blocks(nk, q_hi, block_k):
    """Leading ``block_k``-row KV blocks that intersect key positions
    ``<= q_hi`` — the causal block-skip bound. Shared machinery: the
    training flash forward/backward kernels bound their key walk with it
    (``q_hi`` = the q-tile's last row position), and the decode/chunked-
    prefill kernel (ops/pallas/decode_attention.py) reuses it with
    ``q_hi`` additionally clipped to the slot's live length, so early
    prefill chunks and short sequences alike skip whole blocks instead of
    masking them."""
    return jnp.minimum(nk, (q_hi + block_k) // block_k)


def _causal_band(s, q0, k0, bq, bk):
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q,
                block_k, causal, blk_axis=1):
    # Matmul inputs stay in their native dtype (bf16 in training) with fp32
    # accumulation via preferred_element_type — fp32 MXU issue rate is 1/8
    # of bf16 on TPU, so casting q/k/v up would throttle the whole kernel.
    # Softmax state (m, l, acc) is fp32. blk_axis: which grid axis walks the
    # q-blocks (1 = folded (BH, nq) grid, 2 = bshd (B, H, nq) grid).
    qi = pl.program_id(blk_axis)
    q = q_ref[0]  # [bq, D]
    seq_k = k_ref.shape[1]
    nk = seq_k // block_k
    if causal:
        # key blocks that intersect rows <= this q block's last row
        nk = causal_kv_blocks(nk, (qi + 1) * block_q - 1, block_k)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_band(s, qi * block_q, j * block_k, block_q, block_k)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l

    bq, d = q.shape
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = lax.fori_loop(0, nk, body, (acc0, m0, l0))
    o_ref[0] = (acc / l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(l), (bq, LANE))


def _fwd(q, k, v, scale, causal, block_q, block_k, layout="folded"):
    """folded: q [BH,Sq,D] -> (out [BH,Sq,D], lse [BH,Sq,LANE]).
    bshd/merged: q [B,Sq,H,D] -> (out [B,Sq,H,D], lse [B,Sq,H,LANE]).
    LSE is the broadcast-lane fp32 layout. Sq and Sk may differ
    (ring-attention half blocks); causal requires Sq == Sk (aligned
    positions)."""
    sq, sk = q.shape[1], k.shape[1]
    d = q.shape[-1]
    assert not causal or sq == sk
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    post = lambda out, lse: (out, lse)
    if layout == "merged":
        # [B, S, H, D] viewed as [B, S, H*D] (free: minor dims merge), the
        # head index a grid axis selecting a D-wide lane slice — needs
        # D % 128 == 0 to satisfy Mosaic's lane tiling, and in exchange the
        # kernels consume the model layout with ZERO transpose copies.
        b, h = q.shape[0], q.shape[2]
        q, k, v = (x.reshape(x.shape[0], x.shape[1], h * d)
                   for x in (q, k, v))
        grid = (b, h, sq // bq)
        blk_axis = 2
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda b_, hh, i: (b_, i, hh)),
            pl.BlockSpec((1, sk, d), lambda b_, hh, i: (b_, 0, hh)),
            pl.BlockSpec((1, sk, d), lambda b_, hh, i: (b_, 0, hh)),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, d), lambda b_, hh, i: (b_, i, hh)),
            pl.BlockSpec((1, bq, LANE), lambda b_, hh, i: (b_, i, hh)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b, sq, h * d), q.dtype),
            jax.ShapeDtypeStruct((b, sq, h * LANE), jnp.float32),
        ]
        post = lambda out, lse: (out.reshape(b, sq, h, d),
                                 lse.reshape(b, sq, h, LANE))
    elif layout == "folded":
        bh = q.shape[0]
        grid = (bh, sq // bq)
        blk_axis = 1
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LANE), lambda b, i: (b, i, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, LANE), jnp.float32),
        ]
    else:
        b, h = q.shape[0], q.shape[2]
        grid = (b, h, sq // bq)
        blk_axis = 2
        in_specs = [
            pl.BlockSpec((1, bq, None, d), lambda b, hh, i: (b, i, hh, 0)),
            pl.BlockSpec((1, sk, None, d), lambda b, hh, i: (b, 0, hh, 0)),
            pl.BlockSpec((1, sk, None, d), lambda b, hh, i: (b, 0, hh, 0)),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, None, d), lambda b, hh, i: (b, i, hh, 0)),
            pl.BlockSpec((1, bq, None, LANE), lambda b, hh, i: (b, i, hh, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
            jax.ShapeDtypeStruct((b, sq, h, LANE), jnp.float32),
        ]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=bq, block_k=bk,
                          causal=causal, blk_axis=blk_axis),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        name="flash_fwd",
        out_shape=out_shape,
    )(q, k, v)
    return post(out, lse)


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, *,
                   scale, block_q, block_k, causal, blk_axis=1):
    qi = pl.program_id(blk_axis)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0][:, 0:1]
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=1, keepdims=True)
    seq_k = k_ref.shape[1]
    nk = seq_k // block_k
    if causal:
        nk = causal_kv_blocks(nk, (qi + 1) * block_q - 1, block_k)

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_band(s, qi * block_q, j * block_k, block_q, block_k)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, nk, body, jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                    dk_ref, dv_ref, *, scale, block_q, block_k, causal,
                    blk_axis=1):
    kj = pl.program_id(blk_axis)
    k = k_ref[0]  # [bk, D]
    v = v_ref[0]
    seq_q = q_ref.shape[1]
    nq = seq_q // block_q
    # first q block that can see this k block
    j0 = (kj * block_k) // block_q if causal else 0

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        o = o_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q), 0:1]
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=1, keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_band(s, i * block_q, kj * block_k, block_q, block_k)
        p = jnp.exp(s - lse)
        pt = p.astype(do.dtype)
        dv = dv + jax.lax.dot_general(pt, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros(k.shape, jnp.float32)
    dk, dv = lax.fori_loop(j0, nq, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, layout, res, dout):
    q, k, v, out, lse_c = res
    sq, sk = q.shape[1], k.shape[1]
    d = q.shape[-1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    # Residuals carry the compact (lane-less) LSE (the broadcast LANE layout
    # is 128x larger, which matters when a remat policy saves it);
    # re-broadcast to the Mosaic-tileable layout here, transiently.
    lse = jnp.broadcast_to(lse_c[..., None], lse_c.shape + (LANE,))

    if layout == "folded":
        bh = q.shape[0]
        dq_grid, dkv_grid, blk_axis = (bh, sq // bq), (bh, sk // bk), 1

        def spec(n, lane=False):  # block of n rows (or whole axis), d/LANE wide
            w = LANE if lane else d
            if n is None:  # whole seq axis
                return pl.BlockSpec((1, sq, w), lambda b, i: (b, 0, 0))
            return pl.BlockSpec((1, n, w), lambda b, i: (b, i, 0))

        def kspec(n):
            if n is None:
                return pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0))
            return pl.BlockSpec((1, n, d), lambda b, i: (b, i, 0))

        dq_shape = jax.ShapeDtypeStruct((bh, sq, d), q.dtype)
        dkv_shape = [jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
                     jax.ShapeDtypeStruct((bh, sk, d), v.dtype)]
    elif layout == "merged":
        b, h = q.shape[0], q.shape[2]
        hd = h * d
        q, out, dout = (x.reshape(b, sq, hd) for x in (q, out, dout))
        k, v = (x.reshape(b, sk, hd) for x in (k, v))
        lse = lse.reshape(b, sq, h * LANE)
        dq_grid, dkv_grid, blk_axis = (b, h, sq // bq), (b, h, sk // bk), 2

        def spec(n, lane=False):
            w = LANE if lane else d
            if n is None:
                return pl.BlockSpec((1, sq, w), lambda b_, hh, i: (b_, 0, hh))
            return pl.BlockSpec((1, n, w), lambda b_, hh, i: (b_, i, hh))

        def kspec(n):
            if n is None:
                return pl.BlockSpec((1, sk, d), lambda b_, hh, i: (b_, 0, hh))
            return pl.BlockSpec((1, n, d), lambda b_, hh, i: (b_, i, hh))

        dq_shape = jax.ShapeDtypeStruct((b, sq, hd), q.dtype)
        dkv_shape = [jax.ShapeDtypeStruct((b, sk, hd), k.dtype),
                     jax.ShapeDtypeStruct((b, sk, hd), v.dtype)]
    else:
        b, h = q.shape[0], q.shape[2]
        dq_grid, dkv_grid, blk_axis = (b, h, sq // bq), (b, h, sk // bk), 2

        def spec(n, lane=False):
            w = LANE if lane else d
            if n is None:
                return pl.BlockSpec((1, sq, None, w),
                                    lambda b, hh, i: (b, 0, hh, 0))
            return pl.BlockSpec((1, n, None, w),
                                lambda b, hh, i: (b, i, hh, 0))

        def kspec(n):
            if n is None:
                return pl.BlockSpec((1, sk, None, d),
                                    lambda b, hh, i: (b, 0, hh, 0))
            return pl.BlockSpec((1, n, None, d),
                                lambda b, hh, i: (b, i, hh, 0))

        dq_shape = jax.ShapeDtypeStruct((b, sq, h, d), q.dtype)
        dkv_shape = [jax.ShapeDtypeStruct((b, sk, h, d), k.dtype),
                     jax.ShapeDtypeStruct((b, sk, h, d), v.dtype)]

    # operand order is layout-independent; only spec/kspec/grids/shapes vary
    dq_in = [spec(bq), kspec(None), kspec(None), spec(bq), spec(bq),
             spec(bq, lane=True)]
    dq_out = spec(bq)
    dkv_in = [spec(None), kspec(bk), kspec(bk), spec(None), spec(None),
              spec(None, lane=True)]
    dkv_out = [kspec(bk), kspec(bk)]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=bq, block_k=bk,
                          causal=causal, blk_axis=blk_axis),
        grid=dq_grid, in_specs=dq_in, out_specs=dq_out, out_shape=dq_shape,
        name="flash_bwd_dq",
    )(q, k, v, out, dout, lse)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=bq,
                          block_k=bk, causal=causal, blk_axis=blk_axis),
        grid=dkv_grid, in_specs=dkv_in, out_specs=dkv_out,
        name="flash_bwd_dkv",
        out_shape=dkv_shape,
    )(q, k, v, out, dout, lse)
    if layout == "merged":  # back to the [B, S, H, D] primal shape (free)
        b, h = dq.shape[0], dq.shape[-1] // d
        dq = dq.reshape(b, sq, h, d)
        dk = dk.reshape(b, sk, h, d)
        dv = dv.reshape(b, sk, h, d)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------------- #


def _check_layout(layout: str, d: int | None = None) -> None:
    if layout not in ("folded", "bshd", "merged"):
        raise ValueError(
            f"unknown flash layout {layout!r} (folded|bshd|merged)")
    if layout == "merged" and d is not None and d % LANE:
        raise ValueError(
            f"flash layout 'merged' needs head_dim % {LANE} == 0 (the head "
            f"slice must be a whole lane tile); got head_dim={d}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, scale, causal, block_q, block_k, layout):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k, layout)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, layout):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, layout)
    # checkpoint_name lets a selective remat policy (llama.layers_forward,
    # remat="save_attn") keep out+lse across the backward, so rematerialized
    # backward passes skip the flash forward kernel entirely.
    out = checkpoint_name(out, "flash_out")
    lse_c = checkpoint_name(lse[..., 0], "flash_lse")
    return out, (q, k, v, out, lse_c)


_flash_core.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, scale: float | None = None, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    layout: str = "folded"):
    """q, k, v: [B, S, H, D] with equal head counts. Returns [B, S, H, D].
    layout="merged" (head_dim % 128 == 0 only) and layout="bshd"
    (interpret-mode only; Mosaic rejects it on hardware) run the kernels on
    the model layout directly with no fold copies; "folded" is the
    always-available default."""
    b, s, h, d = q.shape
    _check_layout(layout, d)
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if layout in ("bshd", "merged"):
        return _flash_core(q, k, v, float(scale), causal, block_q, block_k,
                           layout)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = _flash_core(fold(q), fold(k), fold(v), float(scale), causal,
                      block_q, block_k, "folded")
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def flash_block_grads(q, k, v, out, lse, dout, scale: float,
                      causal: bool = True,
                      block_q: int | None = None,
                      block_k: int | None = None,
                      layout: str = "folded"):
    """Gradients of one attention block given an externally-merged (global)
    out/lse — the ring-attention backward building block (the ring re-derives
    each block's true share of the global softmax as exp(s - lse_global),
    reference context_parallel.py:112-155). q/out/dout are [B, Sq, H, D],
    k/v are [B, Sk, H, D] (Sq != Sk allowed for ring half-blocks, non-causal
    only); lse is [B, Sq, H] fp32. Returns (dq, dk, dv)."""
    b, sq, h, d = q.shape
    _check_layout(layout, d)
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    if layout in ("bshd", "merged"):
        return _bwd(scale, causal, block_q, block_k, layout,
                    (q, k, v, out, lse), dout)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
    lse_c = lse.transpose(0, 2, 1).reshape(b * h, sq)
    dq, dk, dv = _bwd(scale, causal, block_q, block_k, "folded",
                      (fold(q), fold(k), fold(v), fold(out), lse_c),
                      fold(dout))
    unfold = lambda x: x.reshape(b, h, x.shape[1], d).transpose(0, 2, 1, 3)
    return unfold(dq), unfold(dk), unfold(dv)


def flash_attention_with_lse(q, k, v, scale: float | None = None,
                             causal: bool = True,
                             block_q: int | None = None,
                             block_k: int | None = None,
                             layout: str = "folded"):
    """Forward-only variant returning (out [B,Sq,H,D], lse [B,Sq,H]) — the
    building block for ring attention's LSE merge. Sq != Sk allowed
    (non-causal only)."""
    b, s, h, d = q.shape
    _check_layout(layout, d)
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if layout in ("bshd", "merged"):
        out, lse = _fwd(q, k, v, float(scale), causal, block_q, block_k,
                        layout)
        return out, lse[..., 0]
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
    out, lse = _fwd(fold(q), fold(k), fold(v), float(scale), causal,
                    block_q, block_k, "folded")
    return (out.reshape(b, h, s, d).transpose(0, 2, 1, 3),
            lse[:, :, 0].reshape(b, h, s).transpose(0, 2, 1))
