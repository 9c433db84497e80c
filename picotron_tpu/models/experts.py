"""This chip's share of an expert layer: which of a router's experts are
held here, their part of the routed sum (every held expert over every row,
as a loop or, where ``takes_pipelined``, as one pipelined pass; from
``takes_grouped`` rows up each expert over its own rows only), and what of
it is counted. The expert blocks (``deepseek_v32``, ``granite_hybrid``, ``afmoe``, ``mimo_v2``,
``keye_vl2``, ``nemotron_h``) route over the router's whole width (``route`` is the router
of five of them: sigmoid scores and a correction bias for four, softmax
scores and no bias for ``keye_vl2``) and hand the choice here; the tree's leaves are named alike in
all: ``w1``/``w3``/``w2`` ``[held, ...]`` a layer (the group's ``UNSLICED``
stacks), ``ws_gate``/``ws_up``/``ws_down`` the shared expert, where the
block has one.

An expert has one of two forms, and the tree says which: with a ``w3`` leaf
(and a ``ws_gate``) the gated SwiGLU of three matrices, ``(silu(x w1) * (x
w3)) w2``; without, two matrices and ``relu(x w1)^2 w2`` (``nemotron_h``).
All three orders run either (``expert``; the kernels' ``_expert``), and
nothing but those two functions knows the form. ``nemotron_h``'s routed
experts also see another input than its shared expert (a latent the block
projects into and out of): ``share``'s ``routed_in``/``routed_out``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.ops.pallas import grouped_experts as grouped
from picotron_tpu.utils import on_tpu

# a group's leaves the layer scan does not slice a layer at a time: the
# layer function is handed the whole stack and its row in it (``lp["row"]``);
# a tree of two-matrix experts has no ``w3``
UNSLICED = ("w1", "w3", "w2")
# what ``share`` counts of an expert layer's step, the head of every expert
# block's ``STAT_NAMES``: assignments that landed on an expert held here,
# held experts a row chose, the step itself, expert-rows pushed through the
# held experts (padding and rows that chose another expert included), the
# step again where its rows took the pipelined pass (``takes_pipelined``)
STAT_NAMES = ("moe_assignments", "moe_experts_hit", "moe_layer_steps",
              "moe_expert_rows", "moe_pipelined_steps")
# rows an expert at which its matmuls take as long as its bfloat16 weights'
# read on a v5e: 197 TFLOP/s / 819 GB/s
RIDGE_ROWS = 240
# rows one grouped call keeps in VMEM (and their float32 sum beside them)
GROUP_ROWS = 512


def route(scores, bias, *, k: int, n_group: int = 1, topk_group: int = 1,
          scale: float = 1.0, eps: float = 0.0) -> tuple:
    """(experts [N, k] int32, weights [N, k] float32) from the router's
    scores [N, width], a sigmoid's or a softmax's (``keye_vl2``: with a zero
    ``bias``): the choice is made on ``scores + bias`` (with
    ``n_group`` > 1: groups by the sum of their two best, the best
    ``topk_group`` groups, the best experts among them; ties to the lower
    index), the weights are the unbiased scores of the chosen, normalised to
    sum 1 (``+ eps``), times ``scale``."""
    N, W = scores.shape
    choice = scores + bias
    if n_group > 1:
        groups = choice.reshape(N, n_group, W // n_group)
        group_score = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)
        _, kept = lax.top_k(group_score, topk_group)
        keep = jnp.zeros((N, n_group), bool).at[
            jnp.arange(N)[:, None], kept].set(True)
        choice = jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(N, W)
    _, experts = lax.top_k(choice, k)
    w = jnp.take_along_axis(scores, experts, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps) * scale
    return experts.astype(jnp.int32), w


def held_weights(experts, weights, first: int, count: int):
    """[N, count] float32: each token's weight on each expert held here
    (``first`` onward of the router's width), 0 where the token did not
    choose it. ``experts``/``weights`` [N, experts per token]."""
    held = first + jnp.arange(count)
    return jnp.sum(jnp.where(experts[:, :, None] == held[None, None, :],
                             weights[:, :, None], 0.0), axis=1)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def relu2(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def expert(x, *ws):
    """One expert over ``x``, its form by the count of its matrices."""
    return swiglu(x, *ws) if len(ws) == 3 else relu2(x, *ws)


def expert_leaves(lp) -> list:
    """The names of a layer's expert matrices, in ``expert``'s order."""
    return [n for n in UNSLICED if n in lp]


def takes_grouped(rows: int) -> bool:
    """Whether ``rows`` rows go through the held experts grouped. The loop
    runs every held expert over every row: ``6 H I`` FLOPs an expert-row
    against ``6 H I`` bytes of bfloat16 weights an expert, so below the
    chip's ridge (``RIDGE_ROWS``) most of its time is the weights' read,
    which the grouped path pays too, and above it its time grows with ``rows
    x held`` where the router asked for ``rows x experts a token x held /
    width`` (Granite's 512-row chunk: 18,432 expert-rows for 2,560
    assignments; a layer's loop 2.37 ms on the chip, grouped 1.07, the read
    alone 0.83; at 256 rows 1.66 and 1.03: PERF.md section 6, PR 43). From
    the ridge up the rows go grouped: the 256-row bucket and the 512-row
    chunk, not the 128-row bucket, a decode block's or a verify's rows
    (8-64), whose every held expert is read at every step as before, by
    the loop or by the pipelined pass (``takes_pipelined``: what a kernel
    wins there is its pipeline's, not the grouping's). Static, and of the
    shape alone: no caller and no option chooses."""
    return rows >= RIDGE_ROWS


def group_rows(w_held, tile: int) -> tuple:
    """The (row, held expert) assignments of ``w_held`` [N, held] (weight >
    0) ordered by expert, in tiles of ``tile`` slots that belong to one
    expert each (a group's last tile is padded): (rank [N, held] int32, a
    row's place among its expert's rows, -1 where it did not choose it;
    tile_expert, tile_base [tiles] int32, each tile's expert and its first
    slot's rank; tiles to run). No assignment is dropped at any routing:
    ``tiles`` = held x ceil(N / tile) holds every expert chosen by every
    row, and the tiles past the count repeat the last that runs."""
    N, E = w_held.shape
    mask = w_held > 0
    rank = jnp.where(mask, jnp.cumsum(mask, axis=0, dtype=jnp.int32) - 1, -1)
    tiles = (jnp.sum(mask, axis=0, dtype=jnp.int32) + tile - 1) // tile
    ends = jnp.cumsum(tiles)
    t = jnp.minimum(jnp.arange(E * -(-N // tile), dtype=jnp.int32),
                    jnp.maximum(ends[-1] - 1, 0))
    expert = jnp.minimum(
        jnp.sum(t[:, None] >= ends[None, :], axis=1, dtype=jnp.int32), E - 1)
    base = (t - (ends - tiles)[expert]) * tile
    return rank, expert, base, ends[-1]


def _stacks(lp) -> tuple:
    """(this layer's row in them, int32 [1]; ``w1``[, ``w3``], ``w2`` as
    stacks [layers, held, ...]) of a layer's leaves: the group's own stacks
    beside ``lp["row"]``, or the layer's matrices as a stack of one."""
    row = lp.get("row")
    stacks = [lp[n] if row is not None else lp[n][None]
              for n in expert_leaves(lp)]
    return jnp.asarray(0 if row is None else row, jnp.int32)[None], stacks


def _grouped(x, w_held, lp) -> tuple:
    """``routed_experts`` for the rows ``takes_grouped`` sends here: (the
    float32 sum, expert-rows run). The rows go ``GROUP_ROWS`` at a time
    (they and their float32 sum stay in VMEM meanwhile), each stretch
    grouped by itself and reading every held expert it has a row for
    once."""
    N, H = x.shape
    row, stacks = _stacks(lp)
    tile = grouped.TILE
    rows = min(GROUP_ROWS, -(-N // tile) * tile)
    pad = -N % rows
    x = jnp.pad(x, ((0, pad), (0, 0)))
    w_held = jnp.pad(w_held, ((0, pad), (0, 0)))
    with jax.named_scope("grouped"):
        rank, expert, base, tiles = jax.vmap(
            lambda w: group_rows(w, tile))(
                w_held.reshape(-1, rows, w_held.shape[1]))
        meta = jnp.concatenate((row, tiles))
        y = grouped.grouped_swiglu(
            x, w_held, rank.reshape(w_held.shape), expert.reshape(-1),
            base.reshape(-1), meta, *stacks, rows=rows, tile=tile,
            interpret=not on_tpu())
    return y[:N], jnp.sum(tiles) * tile


def takes_pipelined(rows: int, H: int, I: int, itemsize: int) -> bool:
    """Whether ``rows`` rows below the ridge go through the held experts as
    one pipelined pass and not the loop: the same work in the same order,
    every held expert read at every step, so the choice is of speed alone.
    The loop is three fusions an expert, each of which starts with an empty
    pipeline: 4-6 us lost a fusion whatever its matrix's size, a third of
    the time where the matrix is Granite's 6.3 MB (7.7 us at HBM speed), a
    seventh where it is DeepSeek's 29.4 MB; the pass fetches the next
    expert's matrices under this one's matmuls. It takes the shapes whose
    three matrices go whole through the kernel's weight budget
    (``grouped.fits``: one grid step an expert, every DMA one contiguous
    matrix), which of the four served blocks is Granite's: a layer's routed
    sum 1.286 -> 0.926 ms at 64 rows on the chip (8 rows 1.144 -> 0.923, 200
    rows 1.430 -> 0.944) and the cell +9 to +14 % (PERF.md section 6, PR
    44). A wider expert would go a block of its width at a time: read that
    way Trinity's cell gained 2-3 %, MiMo's LOST 1 % (its loop's fusions sit
    closer together in the cell than alone) and DeepSeek's pass was behind
    its loop already alone, so they keep the loop. Static, and of the shapes
    alone: no caller, no option and no model's name chooses."""
    return not takes_grouped(rows) and grouped.fits(H, I, itemsize)


def _pipelined(x, w_held, lp):
    """``routed_experts`` for the rows ``takes_pipelined`` sends here: the
    float32 sum (the expert-rows are the loop's, rows x held)."""
    N = x.shape[0]
    # whole sublane tiles of the dtype; the rows added weigh 0
    pad = ((0, -N % (32 // x.dtype.itemsize)), (0, 0))
    row, stacks = _stacks(lp)
    with jax.named_scope("pipelined"):
        y = grouped.pipelined_swiglu(jnp.pad(x, pad), jnp.pad(w_held, pad),
                                     row, *stacks, interpret=not on_tpu())
    return y[:N]


def routed_experts(x, w_held, lp) -> tuple:
    """(``sum_e w_held[:, e] * E_e(x)`` over the experts held here, float32
    [N, H]; the expert-rows it ran; whether the pipelined pass ran them).
    Three orders of the same work, chosen by ``takes_grouped(N)`` and, below
    it, ``takes_pipelined``:

    - the loop, one expert after the other, each over every row (``N x
      held`` expert-rows). Every held expert runs at every step, chosen or
      not, as in the deployment this share is cut from (there each has
      tokens at every step): skipping the ones no token chose (``lax.cond``)
      made a decode step's time follow the seed's routing (8 tokens reach
      1.5-2.2 of DeepSeek's 8; measured 450-467 tokens/s over seeds), which
      is a property of the cut, not of the model. That is about which
      experts' weights a step reads, and the rows below the ridge (a decode
      block, a verify, the small buckets) keep it on both of their orders;
    - the pipelined pass (``ops/pallas/grouped_experts.py::
      pipelined_swiglu``): the loop's work in the loop's order, every held
      expert over every row, expert 0 first, as ONE kernel whose grid is the
      held experts, so that the next expert's matrices are fetched under
      this one's matmuls (the loop is three fusions an expert, each of which
      starts with an empty pipeline and ends draining it), where an
      expert's three matrices go whole through the kernel;
    - grouped (``grouped_swiglu`` there): each held expert over
      its own rows only, in tiles of 128 (the assignments, whatever their
      skew, plus at most a tile of padding a group), the same matmuls in
      the same precisions and the same float32 sum of a row's experts, in
      another order. What no longer runs is an expert over rows that chose
      another: 36 x 512 expert-rows for the 2,560 a Granite chunk's router
      asks for. An expert no row of the chunk chose is not read there.

    With a ``row`` entry, ``lp``'s expert leaves are the group's whole
    stacks (``UNSLICED``) and this layer is that row of them: each expert's
    matrices are then read in place on every path (a layer's slice of the
    stack, handed to any, is a copy of all of them)."""
    N, n = w_held.shape
    if takes_grouped(N):
        return _grouped(x, w_held, lp) + (jnp.zeros((), jnp.int32),)
    run = jnp.asarray(N * n, jnp.int32)
    if takes_pipelined(*x.shape, lp["w1"].shape[-1], lp["w1"].dtype.itemsize):
        return _pipelined(x, w_held, lp), run, jnp.ones((), jnp.int32)
    row = lp.get("row")

    def weights(name, e):
        w = lp[name]
        return w[e] if row is None else w[row, e]

    names = expert_leaves(lp)

    def one(acc, xs):
        w, e = xs
        y = expert(x, *(weights(n, e) for n in names))
        return acc + y.astype(jnp.float32) * w[:, None], None

    acc, _ = lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                      (w_held.T, jnp.arange(n, dtype=jnp.int32)))
    return acc, run, jnp.zeros((), jnp.int32)


def share(lp, x2, w_held, routed_in=None, routed_out=None) -> tuple:
    """(the held experts' part of the routed sum + the shared expert
    [N, H], the layer step's counts in the order of ``STAT_NAMES``) for
    tokens ``x2`` [N, H] weighted ``w_held`` [N, held] (rows that are not
    live: all 0). A layer whose leaves hold no ``ws_up`` has no shared
    expert (``mimo_v2``, ``keye_vl2``); one with ``ws_gate`` a gated one,
    one without a ``relu2``. Where the routed experts do not see what the
    shared expert sees (``nemotron_h``: a latent), ``routed_in`` [N, width]
    is their rows and ``routed_out`` takes their sum back to [N, H] before
    the shared expert's is added (linear, so the chips' shares still add
    up)."""
    with jax.named_scope("moe_experts"):
        y, run, pipelined = routed_experts(
            x2 if routed_in is None else routed_in, w_held, lp)
    y = y.astype(x2.dtype)
    if routed_out is not None:
        y = routed_out(y)
    if "ws_up" in lp:
        with jax.named_scope("shared_expert"):
            y = y + expert(x2, *(lp[n] for n in ("ws_gate", "ws_up",
                                                 "ws_down") if n in lp))
    assigned = jnp.sum(w_held > 0, dtype=jnp.int32)
    hit = jnp.sum(jnp.any(w_held > 0, axis=0), dtype=jnp.int32)
    return y, (assigned, hit, jnp.ones((), jnp.int32), run, pipelined)
