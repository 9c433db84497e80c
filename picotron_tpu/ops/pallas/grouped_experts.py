"""The held experts as Pallas kernels: grouped, each held expert over
its own rows only (``grouped_swiglu``, below), and pipelined, every held
expert over every row in one pass (``pipelined_swiglu``, at the end: the
loop's work in the loop's order, for the rows below the ridge). An expert
has one of two forms, told by the count of its matrices (``_expert``): three,
the gated SwiGLU ``(silu(x w1) * (x w3)) w2`` the kernels are named for; two,
``relu(x w1)^2 w2`` (``models/nemotron_h.py``). Nothing else of either
kernel knows the form.

``sum_e w[:, e] * E_e(x)`` for rows ``x`` [N, H] and the experts a chip
holds (``w1``/``w3`` [L, E, H, I], ``w2`` [L, E, I, H]: the stacks of every
layer, read where they lie), with the (row, expert) assignments already
ordered by expert (``models/experts.py::group_rows``): tile ``t`` of ``tm``
sorted slots belongs to ONE expert, ``tile_expert[t]``, and holds that
expert's rows of rank ``tile_base[t] .. + tm`` (a group's last tile is
padded: ranks past the group's count match no row).

Grid ``(stretches of rows, tiles, I // ti)``, all sequential. A tile's step
gathers its rows out of its stretch of ``x`` (``models/experts.py::
GROUP_ROWS`` rows, resident in VMEM: one DMA a stretch) with a one-hot matmul (exact:
one 1 a row, float32 accumulation), runs ``silu(x w1) * (x w3)`` and ``@ w2``
a block of the expert's width at a time into a float32 accumulator, rounds
the result to the model's dtype as the loop's ``swiglu`` does, and adds it
into the resident float32 ``[N, H]`` sum at its rows: the transposed one-hot
matmul puts each row's result on its row (exact again), the row's float32
router weight multiplies it there. The layer and the tile's expert reach the
weights' ``index_map`` by scalar prefetch, so a weight block is one DMA out
of the stack and consecutive tiles of one expert do not read it again. The
grid is as long as the routing's worst case (every group a tile over); the
tiles past the stretch's count run nothing and ask for the block already there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.ops.pallas.flash_attention import _pick_block

TILE = 128  # rows a tile: the MXU's own height (fewer rows cost it the same)
# VMEM the two buffers of a step's three weight blocks may take (a v5e core
# has 128 MiB; the rows, their sum and the rest take 30-60 more)
WEIGHT_VMEM = 40 << 20


def fits(H: int, ti: int, itemsize: int) -> bool:
    """Whether a step's three weight blocks, ``ti`` of the expert's width
    each, stay inside ``WEIGHT_VMEM`` twice over (the blocks in use, the
    blocks being fetched). An expert of two matrices is held to the same
    rule: it has room to spare."""
    return 3 * H * ti * itemsize * 2 <= WEIGHT_VMEM


def _block_i(H: int, I: int, itemsize: int) -> int:
    """The widest block of the expert's width that divides it in whole lane
    tiles and ``fits`` (Granite's 768 whole; 256 of DeepSeek's 2048)."""
    ti = I
    while not fits(H, ti, itemsize) and ti % 256 == 0:
        ti //= 2
    return ti


def _expert(x, w_refs):
    """float32 ``E(x)`` over an expert's blocks in VMEM, ``(w1, w3, w2)``:
    ``(silu(x w1) * (x w3)) w2``, or ``(w1, w2)``: ``relu(x w1)^2 w2``.
    Operands of the model's dtype, float32 accumulation, each product
    rounded to the dtype where the loop's ``swiglu`` / ``relu2`` rounds
    it."""
    dtype = x.dtype
    *ups, w2_ref = w_refs
    ups = [jnp.dot(x, ref[...], preferred_element_type=jnp.float32
                   ).astype(dtype).astype(jnp.float32) for ref in ups]
    if len(ups) == 2:
        h = jax.nn.silu(ups[0]) * ups[1]
    else:
        h = jnp.square(jax.nn.relu(ups[0]))
    return jnp.dot(h.astype(dtype), w2_ref[...],
                   preferred_element_type=jnp.float32)


def _kernel(te_ref, tb_ref, meta_ref, rank_ref, rank_t_ref, w_ref, x_ref,
            *refs, tm, hc):
    *w_refs, o_ref, xt_ref, acc_ref = refs
    c, t, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    N, H = x_ref.shape
    E = w_ref.shape[1]
    dtype = x_ref.dtype
    # a one-hot product must not round what it moves
    exact = lax.Precision.HIGHEST if dtype == jnp.float32 else None

    @pl.when((t == 0) & (j == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(t < meta_ref[1 + c])
    def _():
        at = c * pl.num_programs(1) + t
        e, base = te_ref[at], tb_ref[at]

        @pl.when(j == 0)
        def _():
            want = base + lax.broadcasted_iota(jnp.int32, (tm, N), 0)
            pick = (rank_t_ref[pl.ds(e, 1), :] == want).astype(dtype)
            xt_ref[...] = jnp.dot(pick, x_ref[...], precision=exact,
                                  preferred_element_type=jnp.float32
                                  ).astype(dtype)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += _expert(xt_ref[...], w_refs)

        @pl.when(j == pl.num_programs(2) - 1)
        def _():
            mine = lax.broadcasted_iota(jnp.int32, (N, E), 1) == e
            rank = jnp.sum(jnp.where(mine, rank_ref[...], 0), axis=1,
                           keepdims=True)
            w = jnp.sum(jnp.where(mine, w_ref[...], 0.0), axis=1,
                        keepdims=True)
            want = base + lax.broadcasted_iota(jnp.int32, (N, tm), 1)
            put = (rank == want).astype(dtype)
            for k in range(0, H, hc):
                y = acc_ref[:, k:k + hc].astype(dtype)
                o_ref[:, k:k + hc] += w * jnp.dot(
                    put, y, precision=exact,
                    preferred_element_type=jnp.float32)


def grouped_swiglu(x, w_held, rank, tile_expert, tile_base, meta, *ws,
                   rows: int, tile: int, interpret: bool = False):
    """float32 [N, H]: ``sum_e w_held[:, e] * E_e(x)`` over the
    assignments ``rank`` names, ``rows`` rows of ``x`` at a time (N a whole
    number of them, ``rows`` of whole tiles): each stretch's rows and their
    float32 sum stay in VMEM while its tiles run, and reads every expert it
    has a row for once. ``x`` [N, H]; ``w_held`` [N, E] float32; ``rank``
    [N, E] int32, a row's place among its expert's rows of its stretch, -1
    where it did not choose the expert; ``tile_expert``/``tile_base``
    [stretches * tiles] int32, each tile's expert and the rank of its first
    slot (the tiles of a stretch that run nothing repeat its last that
    does); ``meta`` int32 [1 + stretches]: the layer's row in the stacks,
    then each stretch's tiles to run; ``ws`` the expert stacks, ``w1``[,
    ``w3``] [L, E, H, I] and, last, ``w2`` [L, E, I, H]."""
    N, H = x.shape
    w1 = ws[0]
    E, I = w1.shape[1], w1.shape[3]
    ti = _block_i(H, I, w1.dtype.itemsize)
    steps, stretches = I // ti, N // rows
    n_tiles = tile_expert.shape[0] // stretches

    def held(c, t, j, te, tb, meta):
        # past the last tile that runs: the block that is already there
        return te[c * n_tiles + t], jnp.where(t < meta[1 + c], j, steps - 1)

    def up_map(c, t, j, te, tb, meta):
        e, jj = held(c, t, j, te, tb, meta)
        return meta[0], e, 0, jj

    def down_map(c, t, j, te, tb, meta):
        e, jj = held(c, t, j, te, tb, meta)
        return meta[0], e, jj, 0

    stretch = lambda width: pl.BlockSpec((rows, width),
                                         lambda c, t, j, *_: (c, 0))
    itemsize = x.dtype.itemsize
    vmem = (2 * rows * H * (itemsize + 4)  # x, the sum: two buffers each
            + 2 * len(ws) * H * ti * w1.dtype.itemsize
            + tile * H * (itemsize + 4)
            + (16 << 20))  # ranks, weights, one-hots, a column block's sum
    return pl.pallas_call(
        functools.partial(_kernel, tm=tile, hc=_pick_block(H, 1024)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(stretches, n_tiles, steps),
            in_specs=[stretch(E),
                      pl.BlockSpec((E, rows), lambda c, t, j, *_: (0, c)),
                      stretch(E), stretch(H),
                      *[pl.BlockSpec((None, None, H, ti), up_map)] *
                      (len(ws) - 1),
                      pl.BlockSpec((None, None, ti, H), down_map)],
            out_specs=stretch(H),
            scratch_shapes=[pltpu.VMEM((tile, H), x.dtype),
                            pltpu.VMEM((tile, H), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((N, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3, vmem_limit_bytes=vmem),
        interpret=interpret,
        name="grouped_experts",
    )(tile_expert, tile_base, meta, rank, rank.T, w_held, x, *ws)


def _pipelined_kernel(row_ref, w_ref, x_ref, *refs):
    *w_refs, o_ref = refs
    e = pl.program_id(0)

    @pl.when(e == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    y = _expert(x_ref[...], w_refs).astype(x_ref.dtype)
    mine = lax.broadcasted_iota(jnp.int32, w_ref.shape, 1) == e
    w = jnp.sum(jnp.where(mine, w_ref[...], 0.0), axis=1, keepdims=True)
    o_ref[...] += y.astype(jnp.float32) * w


def pipelined_swiglu(x, w_held, row, *ws, interpret: bool = False):
    """float32 [N, H]: ``sum_e w_held[:, e] * E_e(x)``, every held
    expert over every row, expert 0 first, as the loop of ``models/experts.py
    ::routed_experts`` runs it: the grid is the held experts, ALL of them
    whatever ``w_held`` holds, one step each with the expert's
    matrices (``ws``: three or two, ``_expert``) whole (the caller sees that they ``fits``: every DMA is one
    contiguous matrix). The rows and their float32 sum stay in VMEM for the
    whole call, and step ``e``'s matmuls run while step ``e + 1``'s matrices
    are fetched out of the stacks in place (layer ``row`` of ``w1``/``w3``
    [L, E, H, I], ``w2`` [L, E, I, H]), where the loop's three fusions an
    expert each start with an empty pipeline. ``x`` [N, H], N whole sublane
    tiles of its dtype; ``w_held`` [N, E] float32."""
    N, H = x.shape
    w1 = ws[0]
    E, I = w1.shape[1], w1.shape[3]
    held = lambda e, row: (row[0], e, 0, 0)
    rows = lambda width: pl.BlockSpec((N, width), lambda e, row: (0, 0))
    up = pl.BlockSpec((None, None, H, I), held)
    vmem = (2 * N * H * (x.dtype.itemsize + 4)  # x, the sum: two buffers each
            + 2 * len(ws) * H * I * w1.dtype.itemsize + (16 << 20))
    return pl.pallas_call(
        _pipelined_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E,),
            in_specs=[rows(E), rows(H), *[up] * (len(ws) - 1),
                      pl.BlockSpec((None, None, I, H), held)],
            out_specs=rows(H)),
        out_shape=jax.ShapeDtypeStruct((N, H), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=vmem),
        interpret=interpret,
        name="pipelined_experts",
    )(row, w_held, x, *ws)
