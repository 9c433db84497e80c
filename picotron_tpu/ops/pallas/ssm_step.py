"""The one-row recurrence of ``ops/ssm.py`` as one pass over the state, in
place: ``S' = exp(dt A) S + (dt x) (x) B``, ``y = S' C`` on one row of the
STACKED float32 state leaf ``[layers of this kind, slots, heads, d_head,
d_state]``.

The compiler runs ``ssm_step`` between a slice of the layer's row and an
update back as three fusions that walk the state three times (read and
write for the update, a second read for the read-out) at about half the
HBM's speed. Here the leaf is the kernel's operand AND its output
(``input_output_aliases``), the layer's ``row`` a scalar-prefetch operand
the index maps return, and a block of ``(slot, a run of heads)`` is read
once, advanced, written back to where it lay and read out while it is in
VMEM: one read and one write of the row, nothing of the other rows, no copy
of a layer in front of the call or behind it.

``B``/``C`` come as ``ops/ssm.py`` takes them: one row for all heads, a row
a group of neighbouring heads, or a row a head. The only parameter is how
many neighbouring heads share a row, read from the shapes; a head block
never straddles a group, and the ``B``/``C`` block's index map divides the
head block's index by that number.

Every product and sum is float32, elementwise as ``ssm_step`` writes them;
the decay ``exp(dt A)`` and ``dt x`` are formed outside (a slot's head
each, nothing beside the state), so ``dt = 0`` hands the kernel ``1`` and
``0`` and the slot's state comes back bit for bit. Only the order of the
``d_state``-term sum of the read-out may differ from the compiler's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_SUBLANE = 8  # fp32 sublane quantum a block of heads respects
# a block of the state: with its output and the pipeline's two buffers each
# four of them are resident, inside the default scoped VMEM of every chip
BLOCK_BYTES = 1 << 20


def head_block(heads: int, shared: int, head_bytes: int) -> int:
    """Heads a block: the most whose state fits ``BLOCK_BYTES``, a whole
    number of sublanes (or all the heads), dividing the heads, and either
    dividing the ``shared`` heads of a ``B``/``C`` row or made of whole
    such runs."""
    fits = [hb for hb in range(1, heads + 1)
            if heads % hb == 0 and (hb % _SUBLANE == 0 or hb == heads)
            and (shared % hb == 0 or hb % shared == 0)]
    small = [hb for hb in fits if hb * head_bytes <= BLOCK_BYTES]
    return max(small) if small else min(fits)


def _kernel(row_ref, x_ref, d_ref, b_ref, c_ref, s_ref, so_ref, y_ref, *,
            runs):
    """One slot's run of heads: ``x_ref`` [hb, P] (``dt x``), ``d_ref``
    [hb, N] (a head's decay over its lanes), ``b_ref``/``c_ref`` [runs, 1,
    N], ``s_ref``/``so_ref`` [hb, P, N], ``y_ref`` [hb, P]."""
    del row_ref
    hb, P, N = s_ref.shape
    x = x_ref[...].reshape(runs, hb // runs, P)[..., None]
    d = d_ref[...].reshape(runs, hb // runs, 1, N)
    b = b_ref[...].reshape(runs, 1, 1, N)
    c = c_ref[...].reshape(runs, 1, 1, N)
    s = s_ref[...].reshape(runs, hb // runs, P, N)
    s = d * s + x * b
    so_ref[...] = s.reshape(hb, P, N)
    y_ref[...] = jnp.sum(s * c, axis=-1).reshape(hb, P)


def ssm_step_stacked(xs, dt, A, Bm, Cm, leaf, row, *,
                     block_heads: int | None = None,
                     interpret: bool = False) -> tuple:
    """``ops/ssm.py::ssm_step`` on row ``row`` of the stacked state leaf:
    (y [B, 1, heads, d_head] float32 without the skip, the leaf with that
    row advanced and every other row as it was). ``xs`` [B, 1, heads,
    d_head], ``dt`` [B, 1, heads] float32, ``A`` [heads], ``Bm``/``Cm`` [B,
    1, d_state], [B, 1, groups, d_state] or [B, 1, heads, d_state], ``leaf``
    [rows, B, heads, d_head, d_state] float32, ``row`` a traced or static
    index. ``interpret=True`` runs the Pallas interpreter (the CPU path)."""
    B, S, nh, P = xs.shape
    if S != 1:
        raise ValueError(f"ssm_step_stacked is the one-row step, got {S}")
    N = leaf.shape[-1]
    if leaf.shape[1:] != (B, nh, P, N) or leaf.dtype != F32:
        raise ValueError(f"state leaf {leaf.shape} {leaf.dtype} against "
                         f"{B} slots of {nh} heads of {P} x {N} float32")
    groups = 1 if Bm.ndim == 3 else Bm.shape[2]
    if nh % groups or Bm.shape != Cm.shape:
        raise ValueError(f"B {Bm.shape} / C {Cm.shape} rows against "
                         f"{nh} heads")
    shared = nh // groups  # neighbouring heads to a row of B and C
    hb = block_heads or head_block(nh, shared, 4 * P * N)
    if nh % hb or (shared % hb and hb % shared):
        raise ValueError(f"blocks of {hb} heads against {nh} heads, "
                         f"{shared} to a row of B and C")
    runs = max(1, hb // shared)  # rows of B and C a block
    block = 4 * hb * P * N

    x32 = xs[:, 0].astype(F32) * dt[:, 0, :, None]  # [B, nh, P]
    # a head's decay over the state's lanes: a row the kernel lays over the
    # head's d_head rows, as it lays B's
    decay = jnp.broadcast_to(jnp.exp(dt[:, 0] * A)[..., None], (B, nh, N))
    b32 = Bm.astype(F32).reshape(B, groups, 1, N)
    c32 = Cm.astype(F32).reshape(B, groups, 1, N)

    def head_spec(width):
        return pl.BlockSpec((None, hb, width), lambda b, j, row: (b, j, 0))

    # a block of fewer heads than share a row: several blocks to the row
    per_row = max(1, shared // hb)
    row_spec = pl.BlockSpec((None, runs, 1, N),
                            lambda b, j, row: (b, j // per_row, 0, 0))
    state_spec = pl.BlockSpec((None, None, hb, P, N),
                              lambda b, j, row: (row[0], b, j, 0, 0))
    leaf, y = pl.pallas_call(
        functools.partial(_kernel, runs=runs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nh // hb),
            in_specs=[head_spec(P), head_spec(N), row_spec, row_spec,
                      state_spec],
            out_specs=[state_spec, head_spec(P)]),
        out_shape=[jax.ShapeDtypeStruct(leaf.shape, F32),
                   jax.ShapeDtypeStruct((B, nh, P), F32)],
        # operands count the scalar-prefetch one: the leaf is the sixth
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the fewest heads the shapes allow may pass ``BLOCK_BYTES``:
            # the block, its output, two buffers each, the body's values
            vmem_limit_bytes=None if block <= BLOCK_BYTES else 8 * block),
        interpret=interpret,
        name="ssm_step",
    )(jnp.asarray(row, jnp.int32).reshape(1), x32, decay, b32, c32, leaf)
    return y[:, None], leaf
