"""Exact top-k of a row of scores as a mask, without a sort: what a sparse
selection chooses its keys with (``models/deepseek_v32.py``: keys by the
indexer's scores; ``models/minicpm_sala.py``: key blocks by their pooled
scores)."""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


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
    bits = lax.bitcast_convert_type(scores, jnp.int32)
    # order-preserving: float order -> signed int order -> unsigned order
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    key = lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(0x80000000)
    nibble = jnp.arange(1, 16, dtype=jnp.uint32)

    def body(i, thr):
        shift = jnp.uint32(28) - 4 * i.astype(jnp.uint32)
        cands = thr[..., None] | (nibble << shift)  # [B, S, 15], rising
        n = jnp.sum(key[..., None, :] >= cands[..., None], axis=-1,
                    dtype=jnp.int32)
        # the counts fall as the candidates rise: as many candidates have
        # k keys at or above them as the largest such candidate's nibble
        return thr | (jnp.sum(n >= k, axis=-1).astype(jnp.uint32) << shift)

    # the largest value with at least k keys at or above it: the k-th largest
    thr = lax.fori_loop(0, 8, body, jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > thr[..., None]
    ties = key == thr[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    first = jnp.cumsum(ties, axis=-1, dtype=jnp.int32) <= room[..., None]
    return (above | (ties & first)) & valid
