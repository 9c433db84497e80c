"""RoPE on rows as the projections write them, as a Pallas TPU kernel.

``ops.rope.apply_rope`` on q and k [B, S, heads * head_dim], a token's heads
side by side in one row, without leaving that shape: the training stack's
paired flash kernels (ops/pallas/flash_attention.py) read those rows two
heads to 128 lanes, and whatever lays q and k out differently in between
pays a relayout copy of each on the way in and of their gradients on the way
out. XLA has no cheap form of it: through the [.., heads, head_dim] view it
lays heads of 64 out sequence-minor (the half swap is then a move between
sublanes, and a transposing copy follows), and on the rows themselves it
materializes the two shifted copies of a row before it combines them (five
passes over q where one should do; 0.94 ms a layer against the view's 0.39,
PERF.md PR 48).

Here a block is [rows, 128] lanes, whole heads: a head's halves change places
by two lane rotations of the block (the XLU's; cyclic over 128 lanes, which
for heads that divide 128 is cyclic over the pair) and a select, then the
same products and sum an element as ``apply_rope``, in float32, rounded once.
One call takes q and k together. The backward is the same kernel under
``-sin``: the rotation's transpose (the tables' halves are equal, so
``rotate_half(g * sin) == rotate_half(g) * sin``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.ops.pallas.flash_attention import LANE, _pick_block

# a block: 512 rows of 512 lanes (bf16: 512 KB, 1 KB a row's stretch; four of
# them double-buffered beside the angles), run 128 lanes at a time
ROWS, COLS = 512, 512


def _rope_kernel(q_ref, k_ref, cos_ref, sin_ref, qo_ref, ko_ref, *, d):
    cos = cos_ref[...].astype(jnp.float32)  # [rows, LANE]: the table tiled
    sin = sin_ref[...].astype(jnp.float32)
    first = lax.broadcasted_iota(jnp.int32, (1, LANE), 1) % d < d // 2
    for x_ref, o_ref in ((q_ref, qo_ref), (k_ref, ko_ref)):
        for c in range(0, x_ref.shape[-1], LANE):
            x = x_ref[0, :, c:c + LANE].astype(jnp.float32)
            # [-x2, x1] a head: x2 comes from d/2 lanes up, x1 from d/2 down
            rotated = jnp.where(first, -pltpu.roll(x, LANE - d // 2, 1),
                                pltpu.roll(x, d // 2, 1))
            o_ref[0, :, c:c + LANE] = \
                (x * cos + rotated * sin).astype(o_ref.dtype)


def _rope_call(q, k, cos, sin):
    b, s, w = q.shape
    d = cos.shape[-1]
    rows, cols = _pick_block(s, ROWS), _pick_block(w, COLS)
    cos, sin = (jnp.tile(t, (1, LANE // d)) for t in (cos, sin))
    x_spec = pl.BlockSpec((1, rows, cols), lambda i, b_, j: (b_, i, j))
    t_spec = pl.BlockSpec((rows, LANE), lambda i, b_, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_rope_kernel, d=d),
        grid=(s // rows, b, w // cols),  # a row tile's angles fetched once
        in_specs=[x_spec, x_spec, t_spec, t_spec],
        out_specs=[x_spec, x_spec],
        name="rope_rows",
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype)],
    )(q, k, cos, sin)


@jax.custom_vjp
def rope_rows(q, k, cos, sin):
    """q, k: [B, S, heads * D] with equal head counts, D dividing 128 and
    heads * D a whole number of 128 lanes; cos/sin: [S, D]
    (``ops.rope.precompute_rope``). Returns the rotated (q, k)."""
    d = cos.shape[-1]
    assert q.shape == k.shape and LANE % d == 0 and q.shape[-1] % LANE == 0
    return _rope_call(q, k, cos, sin)


def _rope_fwd(q, k, cos, sin):
    return _rope_call(q, k, cos, sin), (cos, sin)


def _rope_bwd(res, g):
    cos, sin = res
    dq, dk = _rope_call(g[0], g[1], cos, -sin)
    return dq, dk, jnp.zeros_like(cos), jnp.zeros_like(sin)


rope_rows.defvjp(_rope_fwd, _rope_bwd)
