"""This chip's share of an expert layer: which of a router's experts are
held here, their part of the routed sum, and what of it is counted. The
expert blocks (``deepseek_v32``, ``granite_hybrid``, ``afmoe``, ``mimo_v2``)
route over the router's whole width (the sigmoid router of three of them is
``route``) and hand the choice here; the tree's leaves are named alike in
all: ``w1``/``w3``/``w2`` ``[held, ...]`` a layer (the group's ``UNSLICED``
stacks), ``ws_gate``/``ws_up``/``ws_down`` the shared expert, where the
block has one."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# a group's leaves the layer scan does not slice a layer at a time: the
# layer function is handed the whole stack and its row in it (``lp["row"]``)
UNSLICED = ("w1", "w3", "w2")


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


def routed_experts(x, w_held, lp):
    """``sum_e w_held[:, e] * E_e(x)`` over the experts held here, float32
    [N, H], one expert after the other. Every held expert runs at every
    step, chosen or not, as in the deployment this share is cut from (there
    each has tokens at every step): skipping the ones no token chose
    (``lax.cond``) made a decode step's time follow the seed's routing (8
    tokens reach 1.5-2.2 of DeepSeek's 8; measured 450-467 tokens/s over
    seeds), which is a property of the cut, not of the model. With a ``row``
    entry, ``lp``'s expert leaves are the group's whole stacks
    (``UNSLICED``) and this layer is that row of them: each expert's
    matrices are then read in place (a layer's slice of the stack, handed
    to the loop over experts, is a copy of all of them)."""
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
    return acc


def share(lp, x2, w_held) -> tuple:
    """(the held experts' part of the routed sum + the shared expert
    [N, H], held assignments, held experts hit) for tokens ``x2`` [N, H]
    weighted ``w_held`` [N, held] (rows that are not live: all 0). A layer
    whose leaves hold no ``ws_gate`` has no shared expert (``mimo_v2``)."""
    with jax.named_scope("moe_experts"):
        y = routed_experts(x2, w_held, lp)
    if "ws_gate" in lp:
        with jax.named_scope("shared_expert"):
            y = y.astype(x2.dtype) + swiglu(x2, lp["ws_gate"], lp["ws_up"],
                                            lp["ws_down"])
    else:
        y = y.astype(x2.dtype)
    assigned = jnp.sum(w_held > 0, dtype=jnp.int32)
    hit = jnp.sum(jnp.any(w_held > 0, axis=0), dtype=jnp.int32)
    return y, assigned, hit
