"""This chip's share of an expert layer: which of a router's experts are
held here, their part of the routed sum (one expert after the other over
every row, or from ``takes_grouped`` rows up each over its own rows only),
and what of it is counted. The expert blocks (``deepseek_v32``, ``granite_hybrid``, ``afmoe``, ``mimo_v2``)
route over the router's whole width (the sigmoid router of three of them is
``route``) and hand the choice here; the tree's leaves are named alike in
all: ``w1``/``w3``/``w2`` ``[held, ...]`` a layer (the group's ``UNSLICED``
stacks), ``ws_gate``/``ws_up``/``ws_down`` the shared expert, where the
block has one."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.ops.pallas import grouped_experts as grouped
from picotron_tpu.utils import on_tpu

# a group's leaves the layer scan does not slice a layer at a time: the
# layer function is handed the whole stack and its row in it (``lp["row"]``)
UNSLICED = ("w1", "w3", "w2")
# what ``share`` counts of an expert layer's step, the head of every expert
# block's ``STAT_NAMES``: assignments that landed on an expert held here,
# held experts a row chose, the step itself, expert-rows pushed through the
# held experts (padding and rows that chose another expert included)
STAT_NAMES = ("moe_assignments", "moe_experts_hit", "moe_layer_steps",
              "moe_expert_rows")
# rows an expert at which its matmuls take as long as its bfloat16 weights'
# read on a v5e: 197 TFLOP/s / 819 GB/s
RIDGE_ROWS = 240
# rows one grouped call keeps in VMEM (and their float32 sum beside them)
GROUP_ROWS = 512


def route(scores, bias, *, k: int, n_group: int = 1, topk_group: int = 1,
          scale: float = 1.0, eps: float = 0.0) -> tuple:
    """(experts [N, k] int32, weights [N, k] float32) from the sigmoid
    scores [N, width]: the choice is made on ``scores + bias`` (with
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
    (8-64), whose every held expert is read at every step as before (what
    the kernel would win there, 1.38 -> 1.03 ms, is its pipeline's and not
    the grouping's: PERF.md section 7). Static, and of the shape alone: no
    caller and no option chooses."""
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


def _grouped(x, w_held, lp) -> tuple:
    """``routed_experts`` for the rows ``takes_grouped`` sends here: (the
    float32 sum, expert-rows run). The rows go ``GROUP_ROWS`` at a time
    (they and their float32 sum stay in VMEM meanwhile), each stretch
    grouped by itself and reading every held expert it has a row for
    once."""
    N, H = x.shape
    row = lp.get("row")
    stacks = [lp[n] if row is not None else lp[n][None] for n in UNSLICED]
    tile = grouped.TILE
    rows = min(GROUP_ROWS, -(-N // tile) * tile)
    pad = -N % rows
    x = jnp.pad(x, ((0, pad), (0, 0)))
    w_held = jnp.pad(w_held, ((0, pad), (0, 0)))
    with jax.named_scope("grouped"):
        rank, expert, base, tiles = jax.vmap(
            lambda w: group_rows(w, tile))(
                w_held.reshape(-1, rows, w_held.shape[1]))
        meta = jnp.concatenate(
            (jnp.asarray(0 if row is None else row, jnp.int32)[None], tiles))
        y = grouped.grouped_swiglu(
            x, w_held, rank.reshape(w_held.shape), expert.reshape(-1),
            base.reshape(-1), meta, *stacks, rows=rows, tile=tile,
            interpret=not on_tpu())
    return y[:N], jnp.sum(tiles) * tile


def routed_experts(x, w_held, lp) -> tuple:
    """(``sum_e w_held[:, e] * E_e(x)`` over the experts held here, float32
    [N, H]; the expert-rows it ran). Two orders of the same work, chosen by
    ``takes_grouped(N)``:

    - the loop, one expert after the other, each over every row (``N x
      held`` expert-rows). Every held expert runs at every step, chosen or
      not, as in the deployment this share is cut from (there each has
      tokens at every step): skipping the ones no token chose (``lax.cond``)
      made a decode step's time follow the seed's routing (8 tokens reach
      1.5-2.2 of DeepSeek's 8; measured 450-467 tokens/s over seeds), which
      is a property of the cut, not of the model. That is about which
      experts' weights a step reads, and the rows below the ridge (a decode
      block, a verify, the small buckets) keep it as it was;
    - grouped (``ops/pallas/grouped_experts.py``): each held expert over
      its own rows only, in tiles of 128 (the assignments, whatever their
      skew, plus at most a tile of padding a group), the same matmuls in
      the same precisions and the same float32 sum of a row's experts, in
      another order. What no longer runs is an expert over rows that chose
      another: 36 x 512 expert-rows for the 2,560 a Granite chunk's router
      asks for. An expert no row of the chunk chose is not read there.

    With a ``row`` entry, ``lp``'s expert leaves are the group's whole
    stacks (``UNSLICED``) and this layer is that row of them: each expert's
    matrices are then read in place on both paths (a layer's slice of the
    stack, handed to either, is a copy of all of them)."""
    if takes_grouped(x.shape[0]):
        return _grouped(x, w_held, lp)
    row = lp.get("row")

    def weights(name, e):
        w = lp[name]
        return w[e] if row is None else w[row, e]

    def one(acc, xs):
        w, e = xs
        y = swiglu(x, weights("w1", e), weights("w3", e), weights("w2", e))
        return acc + y.astype(jnp.float32) * w[:, None], None

    n = w_held.shape[1]
    acc, _ = lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                      (w_held.T, jnp.arange(n, dtype=jnp.int32)))
    return acc, jnp.asarray(x.shape[0] * n, jnp.int32)


def share(lp, x2, w_held) -> tuple:
    """(the held experts' part of the routed sum + the shared expert
    [N, H], the layer step's counts in the order of ``STAT_NAMES``) for
    tokens ``x2`` [N, H] weighted ``w_held`` [N, held] (rows that are not
    live: all 0). A layer whose leaves hold no ``ws_gate`` has no shared
    expert (``mimo_v2``)."""
    with jax.named_scope("moe_experts"):
        y, run = routed_experts(x2, w_held, lp)
    if "ws_gate" in lp:
        with jax.named_scope("shared_expert"):
            y = y.astype(x2.dtype) + swiglu(x2, lp["ws_gate"], lp["ws_up"],
                                            lp["ws_down"])
    else:
        y = y.astype(x2.dtype)
    assigned = jnp.sum(w_held > 0, dtype=jnp.int32)
    hit = jnp.sum(jnp.any(w_held > 0, axis=0), dtype=jnp.int32)
    return y, (assigned, hit, jnp.ones((), jnp.int32), run)
