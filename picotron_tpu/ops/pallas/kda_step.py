"""The one-row gated delta rule of ``ops/kda.py`` as one pass over the state,
in place: on one row of the STACKED float32 state leaf ``[KDA layers, slots,
heads, keys, values]``, a slot's head at a time,

    S'  = Diag(a) S                  a = exp(g)
    u   = b (v - S'^T k)
    S_t = S' + k u^T
    o   = S_t^T q

The compiler runs ``kda_step`` between a slice of the layer's row and an
update back as two fusions that walk the state three times (one reads it
along ``k`` and along ``q``, one reads and writes it). Here, as in
``ssm_step.py``, the leaf is the kernel's operand AND its output
(``input_output_aliases``), the layer's ``row`` a scalar-prefetch operand
the state's index map returns, and a block of ``(slot, a run of heads)`` is
read once, decayed, read along ``k``, the rank-one difference added, written
back to where it lay and read out along ``q`` while it is in VMEM: one read
and one write of the row, nothing of the other rows, no copy of a layer in
front of the call or behind it.

The state lies values-in-lanes, so ``a``, ``k`` and ``q`` vary along its
SUBLANES. They come as they are, rows ``[slots, heads, keys]`` (nothing
padded, nothing packed outside); the kernel transposes a block's ``[heads,
keys]`` tile of each once and lays a head's column over the 128 lanes of its
state. ``v``, ``u`` and ``o`` are rows, and ``b`` comes laid over a row's
lanes as ``ssm_step.py``'s decay does.

Every product and sum is float32, as ``kda_step`` writes them, and the
read-out is taken from the new state (``kda_step``'s ``S'^T q + (k . q) u``
is the same sum in another order). ``exp(g)`` is formed outside, so ``g =
0`` and ``b = 0`` hand the kernel ``1`` and ``0`` and the slot's state comes
back bit for bit. Only the order of the 128-term sums along the keys may
differ from the compiler's; ``u`` feeds the state, so the state agrees with
``kda_step``'s to float32 rounding and not bit for bit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.ops.pallas.ssm_step import BLOCK_BYTES, head_block

F32 = jnp.float32


def _kernel(row_ref, a_ref, k_ref, q_ref, v_ref, b_ref, s_ref, so_ref,
            o_ref):
    """One slot's run of heads: ``a_ref``/``k_ref``/``q_ref`` [hb, K],
    ``v_ref``/``b_ref`` [hb, V] (``b`` over a head's lanes),
    ``s_ref``/``so_ref`` [hb, K, V], ``o_ref`` [hb, V]."""
    del row_ref
    # keys to sublanes, once a block: a head's column is a lane of these
    a, k, q = a_ref[...].T, k_ref[...].T, q_ref[...].T  # [K, hb]
    for h in range(s_ref.shape[0]):
        one = slice(h, h + 1)
        k_h = k[:, one]  # [K, 1], laid over the state's lanes where used
        decayed = a[:, one] * s_ref[h]
        along_k = jnp.sum(decayed * k_h, axis=0, keepdims=True)  # [1, V]
        u = b_ref[one, :] * (v_ref[one, :] - along_k)
        state = decayed + k_h * u
        so_ref[h] = state
        o_ref[one, :] = jnp.sum(state * q[:, one], axis=0, keepdims=True)


def kda_step_stacked(q, k, v, g, b, leaf, row, *,
                     block_heads: int | None = None,
                     interpret: bool = False) -> tuple:
    """``ops/kda.py::kda_step`` on row ``row`` of the stacked state leaf: (o
    [B, 1, heads, values] float32, the leaf with that row advanced and every
    other row as it was). ``q``/``k`` [B, 1, heads, keys], ``v`` [B, 1,
    heads, values], ``g`` [B, 1, heads, keys] float32, ``b`` [B, 1, heads]
    float32, ``leaf`` [rows, B, heads, keys, values] float32, ``row`` a
    traced or static index. ``interpret=True`` runs the Pallas interpreter
    (the CPU path)."""
    B, S, nh, K = k.shape
    if S != 1:
        raise ValueError(f"kda_step_stacked is the one-row step, got {S}")
    V = v.shape[-1]
    if leaf.shape[1:] != (B, nh, K, V) or leaf.dtype != F32:
        raise ValueError(f"state leaf {leaf.shape} {leaf.dtype} against "
                         f"{B} slots of {nh} heads of {K} x {V} float32")
    hb = block_heads or head_block(nh, 1, 4 * K * V)
    if nh % hb:
        raise ValueError(f"blocks of {hb} heads against {nh} heads")
    block = 4 * hb * K * V

    def head_spec(width):
        return pl.BlockSpec((None, hb, width), lambda b, j, row: (b, j, 0))

    state_spec = pl.BlockSpec((None, None, hb, K, V),
                              lambda b, j, row: (row[0], b, j, 0, 0))
    leaf, o = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nh // hb),
            in_specs=[head_spec(K)] * 3 + [head_spec(V)] * 2 + [state_spec],
            out_specs=[state_spec, head_spec(V)]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, F32),
                   jax.ShapeDtypeStruct((B, nh, V), F32)],
        # operands count the scalar-prefetch one: the leaf is the seventh
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the fewest heads the shapes allow may pass ``BLOCK_BYTES``:
            # the block, its output, two buffers each, the body's values
            vmem_limit_bytes=None if block <= BLOCK_BYTES else 8 * block),
        interpret=interpret,
        name="kda_step",
    )(jnp.asarray(row, jnp.int32).reshape(1), jnp.exp(g[:, 0]),
      k[:, 0].astype(F32), q[:, 0].astype(F32), v[:, 0].astype(F32),
      jnp.broadcast_to(b[:, 0, :, None], (B, nh, V)), leaf)
    return o[:, None], leaf
