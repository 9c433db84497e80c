"""Exact top-k of a row of scores without a sort, as a mask or as row
indices: what a sparse selection chooses its keys with
(``models/deepseek_v32.py``, ``models/keye_vl2.py``: keys by the indexer's
scores; ``models/minicpm_sala.py``: key blocks by their pooled scores), and
the gather of the rows so chosen (``gather_rows``: a decode step of either
block). And the learned indexer's scoring of a window's cached keys, which
the two blocks that have one share (``index_scores``, its walk over the live
key blocks, its LayerNorm)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# keys scored by an indexer, and attended under a mask, at a time: bounds the
# [B, S, index heads, keys] products
KEY_BLOCK = 2048
LAYER_NORM_EPS = 1e-6  # the indexer's LayerNorm
LANE = 128  # keys a block of ``chosen_rows``: a register row's lanes


def _ordered(scores):
    """``scores``' bits as unsigned integers that compare as the floats do:
    float order -> signed int order -> unsigned order."""
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)


def _kth_largest(key, k: int):
    """[B, S] uint32: the largest value with at least ``k`` of ``key``
    [B, S, T] at or above it (the k-th largest), found four bits at a
    time."""
    nibble = jnp.arange(1, 16, dtype=jnp.uint32)

    def body(i, thr):
        shift = jnp.uint32(28) - 4 * i.astype(jnp.uint32)
        cands = thr[..., None] | (nibble << shift)  # [B, S, 15], rising
        n = jnp.sum(key[..., None, :] >= cands[..., None], axis=-1,
                    dtype=jnp.int32)
        # the counts fall as the candidates rise: as many candidates have
        # k keys at or above them as the largest such candidate's nibble
        return thr | (jnp.sum(n >= k, axis=-1).astype(jnp.uint32) << shift)

    return lax.fori_loop(0, 8, body, jnp.zeros(key.shape[:-1], jnp.uint32))


def _around_kth(scores, k: int) -> tuple:
    """(above [B, S, T] bool: the keys over the k-th largest score; ties: the
    keys equal to it; room [B, S] int32: how many of the ties the ``k``
    still take)."""
    key = _ordered(scores)
    thr = _kth_largest(key, k)
    above = key > thr[..., None]
    return above, key == thr[..., None], \
        k - jnp.sum(above, axis=-1, dtype=jnp.int32)


def select_keys(scores, k: int):
    """[B, S, T] bool: for each query the ``k`` keys of largest score,
    exact, ties to the lower index; keys whose score is -inf (past the
    query, or a query that sees fewer than ``k``) are never chosen.

    No sort: the k-th largest score is found four bits at a time (eight
    passes over the row, each counting the keys at or above fifteen
    candidates: floats compare like the integers their bits spell, once
    negatives are flipped), everything above it is chosen, and of the keys
    equal to it the first ones, until ``k`` are. An exact ``lax.top_k`` of
    2048 out of 24576 is a whole sort on the TPU: a millisecond a decode
    step and layer, and 60 times that a prefill chunk."""
    T = scores.shape[-1]
    valid = scores > -jnp.inf
    if k >= T:
        return valid
    above, ties, room = _around_kth(scores, k)
    first = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room[..., None]
    return (above | (ties & first)) & valid


def _lane_blocks(mask) -> tuple:
    """(running [.., nb, LANE] float32: the keys set in ``mask`` [.., T] up
    to and with each lane of its block of ``LANE``; per [.., nb] int32: a
    block's count). A product with a triangle of ones, not a ``cumsum``
    (which the TPU runs as a windowed reduction over the whole row: 1 ms a
    decode step of the Keye cell, for each); the values are whole numbers up
    to 128, exact in bfloat16 and summed in float32."""
    *lead, T = mask.shape
    nb = -(-T // LANE)
    m = jnp.pad(mask, [(0, 0)] * len(lead) + [(0, nb * LANE - T)])
    m = m.reshape(*lead, nb, LANE).astype(jnp.bfloat16)
    upto = jnp.triu(jnp.ones((LANE, LANE), jnp.bfloat16))  # [l', l]: l' <= l
    running = jnp.einsum("...nm,ml->...nl", m, upto,
                         preferred_element_type=jnp.float32)
    return running, running[..., -1].astype(jnp.int32)


def chosen_rows(chosen, k: int) -> tuple:
    """(rows [B, S, k'] int32, count [B, S] int32) of a mask ``chosen``
    [B, S, T] with at most ``k`` keys set a query: their indices in rising
    order, then zeros; ``k' = min(k, T)``. What a decode step gathers its
    keys by, where ``select_keys``' mask is what a chunk attends under.

    No sort and no scatter: the keys are counted in blocks of ``LANE``; slot
    ``j`` of the output lies in the block before whose end more than ``j``
    keys are set (a comparison with the blocks' running counts), and in that
    block at the lane before which ``j - (keys before the block)`` are (a
    one-hot product fetches the block's running count a lane, a comparison
    finds the lane). ``k' x (T / 128 + 128)`` comparisons a query where a
    comparison of every slot with every key would be ``k' x T``
    (``_lane_blocks`` has the running counts)."""
    T = chosen.shape[-1]
    k = min(k, T)
    nb = -(-T // LANE)
    within, per = _lane_blocks(chosen)
    ends = jnp.cumsum(per, axis=-1)
    count = ends[..., -1]
    j = jnp.arange(k, dtype=jnp.int32)
    before = ends[..., None, :] <= j[:, None]  # [.., k, nb]: blocks passed
    block = jnp.minimum(jnp.sum(before, axis=-1, dtype=jnp.int32), nb - 1)
    start = jnp.sum(jnp.where(before, per[..., None, :], 0), axis=-1)
    own = block[..., None] == jnp.arange(nb, dtype=jnp.int32)
    running = jnp.einsum("...kn,...nl->...kl", own.astype(jnp.bfloat16),
                         within.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
    lane = jnp.sum(running <= (j - start)[..., None].astype(jnp.float32),
                   axis=-1, dtype=jnp.int32)
    rows = jnp.where(j < count[..., None], block * LANE + lane, 0)
    return rows, jnp.minimum(count, k)


def select_rows(scores, k: int) -> tuple:
    """``select_keys``' choice as ``chosen_rows`` gives it: (rows [B, S,
    min(k, T)] int32 rising, count [B, S] int32), from the same exact
    threshold search. The keys equal to the threshold are counted in blocks
    (``_lane_blocks``) where ``select_keys`` runs a ``cumsum`` over the row:
    the same first ones are kept. A decode step's form: lay the queries so
    that the two minor axes fill a register ([1, slots, T], not [slots, 1,
    T]), every pass over the row is eight times as dense."""
    T = scores.shape[-1]
    valid = scores > -jnp.inf
    if k >= T:
        return chosen_rows(valid, k)
    above, ties, room = _around_kth(scores, k)
    running, per = _lane_blocks(ties)
    before = jnp.cumsum(per, axis=-1) - per  # ties in the blocks before
    nth = (running + before[..., None].astype(jnp.float32)).reshape(
        *ties.shape[:-1], -1)[..., :T]
    first = nth <= room[..., None].astype(jnp.float32)
    return chosen_rows((above | (ties & first)) & valid, k)


def gather_rows(leaf, layer, rows):
    """Rows ``rows`` [B, n] of each slot's strip of ``layer`` of a stacked
    leaf [layers, slots, T, ...], fetched where they lie: [B, n, ...]. A
    row gather along the token axis (an embedding's lookup; a row is 2 KB of
    ``keye_vl2``'s K/V, 1,280 B of ``deepseek_v32``'s latent): nothing else
    of the layer is read."""
    return leaf[jnp.asarray(layer, jnp.int32),
                jnp.arange(leaf.shape[1])[:, None], rows]


# --------------------------------------------------------------------------- #
# the indexer: its LayerNorm, its scores of a window's cached keys
# --------------------------------------------------------------------------- #


def layer_norm(x, w, b):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + LAYER_NORM_EPS)).astype(x.dtype) \
        * w + b


def key_block(src: dict, name: str, layer, t0, n: int):
    """Rows ``t0 .. t0 + n`` of stacked leaf ``name`` [layers, slots, rows,
    ...] at ``layer``, read where they lie: [B, n, ...] ([1, n, ...] of the
    one slot a ``slot`` entry names)."""
    leaf = src[name]
    B = 1 if "slot" in src else leaf.shape[1]
    at = (jnp.asarray(layer, jnp.int32),
          jnp.asarray(src.get("slot", 0), jnp.int32),
          jnp.asarray(t0, jnp.int32)) \
        + (jnp.zeros((), jnp.int32),) * (leaf.ndim - 3)
    return lax.dynamic_slice(leaf, at, (1, B, n) + leaf.shape[3:])[0]


def key_blocks(T: int, block: int = KEY_BLOCK) -> int:
    """Keys handled at a time in a window of ``T``: ``block``, or what of it
    divides the window."""
    return T if T <= block else math.gcd(T, block)


def live_blocks(pos_q, T: int, Tb: int):
    """How many leading blocks of ``Tb`` keys some query at ``pos_q`` can
    see: the rest of the window is never read."""
    return jnp.minimum(jnp.max(pos_q), T - 1) // Tb + 1


def index_scores(qi, wi, src: dict, layer, pos_q, block: int = KEY_BLOCK):
    """The indexer's scores [B, S, T] float32 of every key of the window for
    queries at ``pos_q`` [B, S]: ``sum_h w[h] * ReLU(q^I[h] . k^I[s])`` for
    ``s <= pos_q``, -inf past it. Keys are scored ``block`` at a time, and
    only the blocks a query can see are read.

    ``src["ki"]`` [layers, slots, T / p, p x D] holds ``p`` keys a row, one
    after the other (read off the shapes; ``p == 1``: a key a row): a key
    narrower than a register row's lanes shares its row with its neighbours,
    so that the row is whole lanes (``models/keye_vl2.py``: keys of 64, two
    a row). Such rows are contracted whole: a query head stands in its key's
    lanes of a row that is zero elsewhere, once for each of the ``p`` places
    (an exact zero times a finite key adds an exact zero), and the ``p``
    score rows are laid one key after the other."""
    D = qi.shape[-1]
    p = src["ki"].shape[3] // D
    T = src["ki"].shape[2] * p
    Tb = key_blocks(T, block)
    if p > 1:
        return _index_scores_packed(qi, wi, src, layer, pos_q, Tb, p)

    def scored(j):
        kb = key_block(src, "ki", layer, j * Tb, Tb)  # [B, Tb, D]
        s = jnp.einsum("bshd,btd->bsht", qi, kb,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(s) * wi[..., None], axis=2)  # [B, S, Tb]

    return _walk(scored, pos_q, T, Tb)


def _walk(scored, pos_q, T: int, Tb: int):
    """[B, S, T] float32: ``scored(j)`` [B, S, Tb], the scores of the
    ``j``-th block of ``Tb`` keys, for every block a query at ``pos_q`` can
    see; -inf past a query's own position. A decode step's scores (S == 1)
    are gathered queries-major, [S, B, T], and handed back [B, S, T]: with
    the slots beside the keys the two minor axes fill a register, where
    [slots, 1, T] lies a row of 128 a register, an eighth full, and its
    block writes and masks cost three times the keys' read (a caller that
    swaps the axes back, a decode step of ``keye_vl2.attention`` or
    ``deepseek_v32.attention``, moves nothing). A chunk's (B == 1) are
    gathered as they are returned."""
    B, S = pos_q.shape
    swap = S == 1
    pos = pos_q.T if swap else pos_q

    def body(j, buf):
        s = scored(j)
        s = s.swapaxes(0, 1) if swap else s
        t = j * Tb + jnp.arange(Tb, dtype=jnp.int32)
        s = jnp.where(t[None, None, :] <= pos[..., None], s, -jnp.inf)
        return lax.dynamic_update_slice(buf, s, (0, 0, j * Tb))

    buf = jnp.full((S, B, T) if swap else (B, S, T), -jnp.inf, jnp.float32)
    if T == Tb:
        buf = body(0, buf)
    else:
        buf = lax.fori_loop(0, live_blocks(pos_q, T, Tb), body, buf)
    return buf.swapaxes(0, 1) if swap else buf


def _index_scores_packed(qi, wi, src: dict, layer, pos_q, Tb: int, p: int):
    """``index_scores`` over a ``ki`` leaf of ``p`` keys a row."""
    B, S, h, D = qi.shape
    T = src["ki"].shape[2] * p
    # [B, S, p, heads, p x D]: place i holds the head in lanes i x D ..
    place = jnp.eye(p, dtype=bool)[:, None, :, None]  # [p, 1, p, 1]
    qi = jnp.where(place, qi[:, :, None, :, None, :], 0).reshape(
        B, S, p, h, p * D)

    def scored(j):
        kb = key_block(src, "ki", layer, j * (Tb // p), Tb // p)
        s = jnp.einsum("bsphd,btd->bstph", qi, kb,
                       preferred_element_type=jnp.float32)
        s = jnp.sum(jax.nn.relu(s) * wi[:, :, None, None, :], axis=-1)
        return s.reshape(B, S, Tb)  # key = row x p + place

    return _walk(scored, pos_q, T, Tb)
