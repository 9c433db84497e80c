"""The dense form of ``flash_decode_stacked`` as it stood before PR 63, kept
as a reference: a grid of ``(slots, T / block_t)`` steps, a step past a
slot's walk a repeated block index and a skipped body, K and V blocks through
the Pallas pipeline. The kernel that replaced it walks a slot's live blocks
inside one grid step and must give the same bits on the same inputs, since
the blocks, their order and each block's arithmetic are these
(``tests/test_decode_kernel.py``). Nothing in the package imports this."""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.inference.kv_cache import _own_lanes, _own_lanes_only
from picotron_tpu.ops.attention import NEG_INF
from picotron_tpu.ops.pallas.decode_attention import (
    _BF16_ROWS,
    _pick_block,
    _stacked_block_rows,
    _stacked_blocks,
)
from picotron_tpu.ops.pallas.flash_attention import _dot_nt, _scale_folds


def _kernel(len_ref, layer_ref, *refs, scale, block_t, rows, pg, max_nb,
            sink):
    del layer_ref  # consumed by the index maps
    q_ref, refs = refs[0], refs[1:]
    sink_ref, refs = (refs[0], refs[1:]) if sink else (None, refs)
    k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, own_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    L = len_ref[b]
    nb = _stacked_blocks(L, block_t, max_nb)
    nq, cols = own_ref.shape

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if sink:
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        head_row = lax.broadcasted_iota(jnp.int32, (nq, cols), 0) // pg
        col = lax.broadcasted_iota(jnp.int32, (nq, cols), 1)
        own_ref[...] = jnp.where(col % rows == head_row, col // rows,
                                 jnp.iinfo(jnp.int32).max)

    @pl.when(j < nb)
    def _():
        s = _dot_nt(q_ref[...], k_ref[...])
        if scale is not None:
            s = s * scale
        s = jnp.where(own_ref[...] < L - j * block_t, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    @pl.when(j == max_nb - 1)
    def _():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        o_ref[...] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


def flash_decode_stacked(q, k, v, lengths, scale, layer, *, block_t=None,
                         sink=None):
    """``decode_attention.flash_decode_stacked`` without a ``window``, in
    interpret mode, as the parent of PR 63 had it."""
    B, _, nh, D = q.shape
    nl, _, T, rows, lanes = k.shape
    pack, lanes_v = lanes // D, v.shape[-1]
    pg = nh // rows
    qg = q.reshape(B, rows, pg, D)
    if pack > 1:
        qg = _own_lanes_only(qg, pack)
    qm = qg.reshape(B, nh, lanes)
    if _scale_folds(scale):
        qm, scale = qm * jnp.asarray(scale, qm.dtype), None
    nq = -(-nh // _BF16_ROWS) * _BF16_ROWS
    if nq != nh:
        qm = jnp.pad(qm, ((0, 0), (0, nq - nh), (0, 0)))
    bt = _pick_block(T, block_t) if block_t else _stacked_block_rows(
        T, rows * lanes * k.dtype.itemsize)
    cols, max_nb = bt * rows, T // bt
    prefetch = (lengths.astype(jnp.int32),
                jnp.asarray(layer, jnp.int32).reshape(1))

    def kv_index(b, j, len_ref, layer_ref):
        nb = _stacked_blocks(len_ref[b], bt, max_nb)
        jj = jnp.maximum(jnp.minimum(j, nb - 1), 0)  # past the walk: no DMA
        return (layer_ref[0], b, jj, 0)

    def row_spec(width):
        return pl.BlockSpec((None, nq, width), lambda b, j, *_: (b, 0, 0))

    def kv_spec(width):
        return pl.BlockSpec((None, None, cols, width), kv_index)

    operands, in_specs = [qm], [row_spec(lanes)]
    if sink is not None:
        operands.append(jnp.pad(sink.astype(jnp.float32),
                                (0, nq - nh)).reshape(nq, 1))
        in_specs.append(pl.BlockSpec((nq, 1), lambda b, j, *_: (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block_t=bt, rows=rows, pg=pg,
                          max_nb=max_nb, sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, max_nb),
            in_specs=in_specs + [kv_spec(lanes), kv_spec(lanes_v)],
            out_specs=row_spec(lanes_v),
            scratch_shapes=[pltpu.VMEM((nq, lanes_v), jnp.float32),
                            pltpu.VMEM((nq, 1), jnp.float32),
                            pltpu.VMEM((nq, 1), jnp.float32),
                            pltpu.VMEM((nq, cols), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, nq, lanes_v), q.dtype),
        interpret=True,
    )(*prefetch, *operands, k.reshape(nl, B, T * rows, lanes),
      v.reshape(nl, B, T * rows, lanes_v))
    out = out[:, :nh].reshape(B, 1, rows, pg, lanes_v)
    if pack > 1:
        out = _own_lanes(out, pack)
    return out.reshape(B, 1, nh, lanes_v // pack)
