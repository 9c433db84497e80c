"""Tensor parallelism: the Megatron f/g conjugate collectives, JAX-style.

The reference implements these as autograd.Function pairs over NCCL
(tensor_parallel/tp_communications.py:19-72):

- f = CopyToModelParallelRegion: identity forward, all-reduce backward —
  placed where a replicated activation enters a column-parallel matmul.
- g = ReduceFromModelParallelRegion: all-reduce forward, identity backward —
  placed after a row-parallel matmul whose output shards are partial sums.
- GatherFromModelParallelRegion: all-gather forward, split backward — used to
  gather vocab-sharded logits (tensor_parallel.py:48-50).

Here each is a ~5-line ``jax.custom_vjp`` around ``lax.psum``/``all_gather``
on the 'tp' mesh axis, usable inside ``shard_map``. The reference's async
all-reduce-overlap variant (LinearWithAsyncAllReduce,
tp_communications.py:74-101) needs no equivalent: XLA's latency-hiding
scheduler overlaps the backward all-reduce with the grad-weight matmul
automatically.

The column/row/vocab-parallel *layers* themselves (reference
tensor_parallel.py:54-271) are not classes here — a column-parallel linear is
just ``tp_copy(x) @ w_shard`` and a row-parallel one ``tp_reduce(x @ w_shard)``
in the model (models/llama.py); the vocab-parallel embedding's mask-and-psum
trick (tensor_parallel.py:246-271) lives in models/llama.py:embed_lookup.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax

from picotron_tpu.comm_trace import log as _trace


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_copy(x, axis: str = "tp"):
    """Identity forward / psum backward (Megatron f, tp_communications.py:19-33)."""
    return x


def _tp_copy_fwd(x, axis):
    return x, None


def _tp_copy_bwd(axis, _, g):
    _trace("tp_copy.bwd all_reduce", axis, g)
    return (lax.psum(g, axis),)


tp_copy.defvjp(_tp_copy_fwd, _tp_copy_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_reduce(x, axis: str = "tp"):
    """psum forward / identity backward (Megatron g, tp_communications.py:35-49)."""
    _trace("tp_reduce.fwd all_reduce", axis, x)
    return lax.psum(x, axis)


def _tp_reduce_fwd(x, axis):
    _trace("tp_reduce.fwd all_reduce", axis, x)
    return lax.psum(x, axis), None


def _tp_reduce_bwd(axis, _, g):
    return (g,)


tp_reduce.defvjp(_tp_reduce_fwd, _tp_reduce_bwd)


# --------------------------------------------------------------------------- #
# Sequence parallelism (Megatron-SP): between the TP blocks the activation's
# *sequence* axis is sharded over 'tp' instead of replicated. The f/g pair
# becomes g-bar/f-bar: entering a column-parallel matmul the seq shards are
# all-gathered; leaving a row-parallel matmul the partial sums are
# reduce-scattered back to seq shards (psum = all-gather + reduce-scatter, so
# the wire cost is identical to plain TP while the residual stream, norms and
# saved layer boundaries shrink by 1/tp). The reference only TODOs this
# (utils.py:66 "LayerNorm is also split across TP ranks"); SURVEY.md §2.3
# marks it nearly free in JAX. Norm-weight gradients become partial over the
# local seq shard and are psum'd over 'tp' in the train step
# (train_step.sync_sp_norm_grads).
# --------------------------------------------------------------------------- #


def all_gather_dim(x, axis: str, dim: int):
    """Tiled all-gather along array dimension ``dim`` over mesh axis ``axis``.
    Public building block shared by the SP collectives and the ZeRO-1 param
    all-gather (train_step)."""
    _trace("all_gather", axis, x, extra=f"dim={dim}")
    return lax.all_gather(x, axis, axis=dim, tiled=True)


def all_gather_dim_invariant(x, axis: str, dim: int):
    """``all_gather_dim`` whose result is TYPED replicated over ``axis``
    under shard_map's varying-axes checker — every rank contributes its
    shard and receives the same whole, and there is no legal demotion from
    a varying-typed plain gather. Falls back to the plain gather when the
    trace is not vma-typed (the invariant primitive's vjp demands
    vma-typed operands and fails on a checker-off build). Single home for
    the jax-internal import: consumers are the ZeRO-1 param unsplit
    (train_step) and the gathered CE loss (ops/cross_entropy)."""
    from picotron_tpu.utils import typeof_vma

    if axis in typeof_vma(x):
        # jax 0.9.0 has no public spelling of the invariant gather
        from jax._src.lax.parallel import all_gather_invariant

        _trace("all_gather", axis, x, extra=f"dim={dim} invariant")
        return all_gather_invariant(x, axis, axis=dim, tiled=True)
    return all_gather_dim(x, axis, dim)


def reduce_scatter_dim(x, axis: str, dim: int):
    """Tiled reduce-scatter along array dimension ``dim`` over mesh axis
    ``axis``. Public building block shared by the SP collectives and the
    ZeRO-1 gradient reduce-scatter (train_step)."""
    _trace("reduce_scatter", axis, x, extra=f"dim={dim}")
    return lax.psum_scatter(x, axis, scatter_dimension=dim, tiled=True)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def sp_gather(x, axis: str = "tp", dim: int = 1):
    """Seq all-gather forward / reduce-scatter backward (Megatron-SP g-bar):
    [B, S/tp, ...] -> [B, S, ...] entering a column-parallel region."""
    return all_gather_dim(x, axis, dim)


def _sp_gather_fwd(x, axis, dim):
    return all_gather_dim(x, axis, dim), None


def _sp_gather_bwd(axis, dim, _, g):
    return (reduce_scatter_dim(g, axis, dim),)


sp_gather.defvjp(_sp_gather_fwd, _sp_gather_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def sp_scatter(x, axis: str = "tp", dim: int = 1):
    """Seq reduce-scatter forward / all-gather backward (Megatron-SP f-bar):
    partial-sum [B, S, ...] -> reduced [B, S/tp, ...] leaving a row-parallel
    region. Replaces ``tp_reduce`` when sequence parallelism is on."""
    return reduce_scatter_dim(x, axis, dim)


def _sp_scatter_fwd(x, axis, dim):
    return reduce_scatter_dim(x, axis, dim), None


def _sp_scatter_bwd(axis, dim, _, g):
    return (all_gather_dim(g, axis, dim),)


sp_scatter.defvjp(_sp_scatter_fwd, _sp_scatter_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_gather(x, axis: str = "tp"):
    """All-gather on the last dim forward / take-own-slice backward
    (GatherFromModelParallelRegion, tp_communications.py:51-72)."""
    _trace("tp_gather.fwd all_gather", axis, x)
    return lax.all_gather(x, axis, axis=-1, tiled=True)


def _tp_gather_fwd(x, axis):
    _trace("tp_gather.fwd all_gather", axis, x)
    return lax.all_gather(x, axis, axis=-1, tiled=True), x.shape[-1]


def _tp_gather_bwd(axis, local_dim, g):
    idx = lax.axis_index(axis)
    return (lax.dynamic_slice_in_dim(g, idx * local_dim, local_dim, axis=-1),)


tp_gather.defvjp(_tp_gather_fwd, _tp_gather_bwd)
