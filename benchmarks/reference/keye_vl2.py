"""The plain reference of the Keye-VL-2.0 block (``model_type: "KeyeVL2"``,
the language model of Keye-VL-2.0-30B-A3B): GQA with q/k norm a head and
M-RoPE over three position streams, a learned top-k selection of the keys a
query attends, softmax-routed experts with no shared one in every layer, in
jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no gather, no batching, and nothing imported from
``picotron_tpu``. Attention is the causal softmax as it is written, a block
of query rows at a time against every key up to it, masked to the selected
set.

What it computes (``x`` the normed stream of one sequence; ``N`` RMSNorm
with weight, eps ``rms_norm_eps``; no bias in any projection):

- ``h = E[tokens]``; a layer: ``h += Attn(N1(h))``, then ``h += MoE(N2(h))``;
  ``logits = Nf(h) W_head``, untied;
- ``q = x W_q`` (``num_attention_heads`` of ``head_dim``), ``k = x W_k``, ``v
  = x W_v`` (``num_key_value_heads`` of ``head_dim``); ``q`` and ``k``
  RMS-normed a head (one weight vector each); M-RoPE on the whole head,
  halves paired (``x[i]``, ``x[i + head_dim / 2]``), base ``rope_theta``, no
  scaling: pair ``i``'s angle is ``p_c(i) * theta^(-2i / head_dim)`` with
  ``c(i)`` the temporal, height or width stream by
  ``rope_scaling.mrope_section`` (the pairs each owns, in that order);
- the indexer (``sa_config``): ``q^I = x W^I_q`` (``indexer_num_heads`` x
  ``indexer_head_dim``), ``k^I = LayerNorm(x W^I_k)`` with weight and bias
  (eps 1e-6), both rotated over their whole width by the temporal position
  (halves, base ``rope_theta``), ``w = (x W^I_w) * heads^-0.5 * dim^-0.5``;
  ``I[t, s] = sum_h w[t, h] ReLU(q^I[t, h] . k^I[s])`` for ``s <= t``; the
  selected set of ``t`` is its ``min(topk, t + 1)`` best keys, exact, ties to
  the lower index (a stable sort), the same for every query head;
- ``softmax(q . k / sqrt(head_dim))`` over the selected set, times ``v``,
  through ``W_o``;
- ``s = softmax(x W_r)`` over the router's whole width; the
  ``num_experts_per_tok`` largest, ties to the lower index (a stable sort);
  weights ``s[chosen] / sum`` where ``norm_topk_prob``; the sum over the
  chosen experts *held here* of ``w_e (silu(x W1_e) * (x W3_e)) W2_e``.

Departures from the published description, the program's own and copied here
so that the two can agree:

- the share: ``num_experts`` counts the experts held here, those from
  ``ep_rank * num_experts`` on of a router ``num_experts * ep_size`` wide;
  what the absent experts would add is left out, and the vocabulary is the
  slice the tree holds;
- the indexer's keys are not quantised (the published indexers keep FP8);
- the vision tower and its merger are not held: a request is token ids, and
  ``positions`` (three streams, [3, S] a sequence) is built equal from
  ``tokens`` when none is given;
- every matrix is held ``[in, out]``; the weights are the program's seeded
  random ones.

``forward_logits(..., select=False)`` switches the selection off (every
causal key attended: what the model is while the context is shorter than
``topk``). ``model["_without"]``, a set of names, leaves one part out, for
the tests that hold the program to each: ``"qk_norm"``,
``"softmax_router"`` (a sigmoid's scores in its place).

Parameters come from the system under test a layer at a time (``layer_of``),
each matrix cast to float32 where it is used; a sequence's attention and
logits are taken in blocks of rows, and every layer is waited for, so that
the device's peak stays the program's own.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
LAYER_NORM_EPS = 1e-6
ROW_BLOCK = 2048  # rows of logits at a time, each moved to the host
QUERY_BLOCK = 256  # query rows of attention at a time


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


def _layer_norm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LAYER_NORM_EPS) * w.astype(F32) \
        + b.astype(F32)


def angles(positions, dim: int, theta: float):
    """[.., S, dim / 2] float64: ``positions`` [.., S] times the ``dim / 2``
    inverse frequencies ``theta^(-2i / dim)``."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    return np.asarray(positions, np.float64)[..., None] * inv


def mrope_angles(positions, dim: int, theta: float, section):
    """[S, dim / 2]: pair ``i``'s angle from the stream (``positions``
    [3, S]: temporal, height, width) that owns it by ``section``."""
    owner = np.repeat(np.arange(3), section)  # [dim / 2]
    if owner.size != dim // 2:
        raise ValueError(f"mrope_section {section} does not give the "
                         f"{dim // 2} pairs of a head to three streams")
    return np.take_along_axis(angles(positions, dim, theta),
                              owner[None, None, :], axis=0)[0]


def cos_sin(ang) -> tuple:
    """(cos, sin) float32 of float64 angles: taken before the rounding, so
    that a position of tens of thousands keeps its fast pairs' phase."""
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def _rotate(x, turn):
    """Halves (x[i], x[i + D/2]) of x [S, heads, D] turned by ``turn``, the
    (cos, sin) [S, D / 2] of the angles."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    c, s = turn[0][:, None, :], turn[1][:, None, :]
    return jnp.concatenate([a * c - b * s, a * s + b * c], axis=-1)


@partial(jax.jit, static_argnames=("dims", "eps", "qk_norm"))
def projections(lp, x, ang_h, ang_i, *, dims, eps, qk_norm=True):
    """(q [S, heads, D], k, v [S, kv heads, D], q^I [S, index heads, Di],
    k^I [S, Di], w [S, index heads]) of one sequence."""
    nh, nkv, hd, ih, idim = dims
    S = x.shape[0]
    with jax.default_matmul_precision("highest"):
        q = (x @ lp["wq"].astype(F32)).reshape(S, nh, hd)
        k = (x @ lp["wk"].astype(F32)).reshape(S, nkv, hd)
        v = (x @ lp["wv"].astype(F32)).reshape(S, nkv, hd)
        if qk_norm:
            q = _rms_norm(q, lp["q_norm"], eps)
            k = _rms_norm(k, lp["k_norm"], eps)
        q, k = _rotate(q, ang_h), _rotate(k, ang_h)
        qi = _rotate((x @ lp["wi_q"].astype(F32)).reshape(S, ih, idim),
                     ang_i)
        ki = _layer_norm(x @ lp["wi_k"].astype(F32), lp["ki_norm"],
                         lp["ki_bias"])
        ki = _rotate(ki[:, None, :], ang_i)[:, 0]
        w = (x @ lp["wi_w"].astype(F32)) * (ih ** -0.5 * idim ** -0.5)
        return q, k, v, qi, ki, w


@partial(jax.jit, static_argnames=("rows", "topk"))
def attend_rows(parts, wo, r0, *, rows: int, topk: int):
    """The attention's output [rows, H] of queries ``r0 .. r0 + rows``
    against the whole sequence, and the keys each attended [rows, S].
    ``topk`` 0: no selection, every causal key."""
    q, k, v, qi, ki, w = parts
    with jax.default_matmul_precision("highest"):
        S, nkv, hd = k.shape
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, r0, rows, 0)
        t = r0 + jnp.arange(rows)
        seen = jnp.arange(S)[None, :] <= t[:, None]  # [rows, S]
        if topk:
            index = jnp.einsum("shd,td->sht", cut(qi), ki)
            index = jnp.sum(jax.nn.relu(index) * cut(w)[:, :, None], axis=1)
            index = jnp.where(seen, index, -jnp.inf)
            # rank of every key among its query's: a stable sort puts the
            # lower index first among equals
            order = jnp.argsort(-index, axis=-1, stable=True)
            rank = jnp.argsort(order, axis=-1, stable=True)
            seen = seen & (rank < topk)
        qr = cut(q)
        g = qr.shape[1] // nkv
        z = jnp.einsum("skgd,tkd->skgt", qr.reshape(rows, nkv, g, hd), k) \
            / math.sqrt(hd)
        z = jnp.where(seen[:, None, None, :], z, -jnp.inf)
        o = jnp.einsum("skgt,tkd->skgd", jax.nn.softmax(z, axis=-1), v)
        return o.reshape(rows, -1) @ wo.astype(F32), seen


def attention(lp, x, ang_h, ang_i, model: dict, select: bool = True):
    """([S, H]: the attention of one sequence; the keys each query attended
    as [rows, S] bool blocks when ``model`` asks for them under
    ``_keep_selected``, a by-hand reading)."""
    sa = model["sa_config"]
    dims = (int(model["num_attention_heads"]),
            int(model["num_key_value_heads"]), int(model["head_dim"]),
            int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]))
    S = x.shape[0]
    parts = projections(
        lp, x, ang_h, ang_i, dims=dims, eps=float(model["rms_norm_eps"]),
        qk_norm="qk_norm" not in model.get("_without", ()))
    rows = min(S, QUERY_BLOCK)
    topk = int(sa["topk"]) if select else 0
    blocks, selected = [], []
    for r0 in range(0, S, rows):
        at = min(r0, S - rows)  # the last block steps back to stay whole
        o, seen = attend_rows(parts, lp["wo"], at, rows=rows, topk=topk)
        blocks.append(o[r0 - at:])
        if model.get("_keep_selected"):
            selected.append(np.asarray(seen)[r0 - at:])
    return jnp.concatenate(blocks), selected


@partial(jax.jit, static_argnames=("k", "norm", "softmax"))
def route(x, router, *, k: int, norm: bool, softmax: bool = True):
    """(experts [S, k], weights [S, k]): the ``k`` largest of ``softmax(x
    W_r)``, ties to the lower index; their scores, over their sum where
    ``norm``."""
    with jax.default_matmul_precision("highest"):
        logits = x @ router.astype(F32)
    scores = jax.nn.softmax(logits, axis=-1) if softmax \
        else jax.nn.sigmoid(logits)
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, order, axis=-1)
    return order, w / jnp.sum(w, axis=-1, keepdims=True) if norm else w


@jax.jit
def _swiglu(x, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(x @ w_gate.astype(F32))
                * (x @ w_up.astype(F32))) @ w_down.astype(F32)


def experts(lp, x, model: dict):
    """The routed experts held here: [S, H]. No shared expert."""
    chosen, weights = route(
        x, lp["router"], k=int(model["num_experts_per_tok"]),
        norm=bool(model.get("norm_topk_prob", True)),
        softmax="softmax_router" not in model.get("_without", ()))
    held = int(model["num_experts"])
    first = int(model.get("ep_rank", 0)) * held
    y = jnp.zeros_like(x)
    for e in range(held):
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = y + w[:, None] * _swiglu(x, lp["w1"][e], lp["w3"][e], lp["w2"][e])
    return y


def layer(lp, h, ang_h, ang_i, model: dict, select: bool = True):
    """One layer on one sequence, ``h`` [S, H] float32: (the stream after
    it, the keys each query attended when ``model`` keeps them)."""
    eps = float(model["rms_norm_eps"])
    a, selected = attention(lp, _rms_norm(h, lp["attn_norm"], eps), ang_h,
                            ang_i, model, select)
    h = h + a
    return h + experts(lp, _rms_norm(h, lp["mlp_norm"], eps), model), selected


def layer_of(params, i: int, model: dict, device):
    """Layer ``i`` of the system's tree (one stacked group, ``layers``),
    whole, on ``device``."""
    return jax.device_put(jax.tree.map(lambda v: v[i], params["layers"]),
                          device)


@partial(jax.jit, static_argnames=("eps",))
def head(final_norm, lm_head, h, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, final_norm, eps) @ lm_head.astype(F32)


@jax.jit
def mean_cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def forward_logits(params, tokens, model: dict, device=None, *,
                   positions=None, select: bool = True):
    """Logits [B, S, V] (numpy float32, V the slice of the vocabulary the
    tree holds) of ``tokens`` [B, S]. ``positions`` [3, B, S]: each token's
    temporal, height and width position (None: a text's, all three its
    index)."""
    return np.stack([np.concatenate(rows) for rows in
                     _per_sequence(params, tokens, model, device, None,
                                   positions, select)])


def loss(params, tokens, targets, model: dict, device=None) -> float:
    """Mean next-token cross-entropy over every position, over the sliced
    vocabulary: the mean of the sequences' means."""
    return float(np.mean(_per_sequence(params, tokens, model, device,
                                       np.asarray(targets), None, True)))


def _per_sequence(params, tokens, model, device, targets, positions, select):
    device = device or jax.devices()[0]
    tokens = np.asarray(tokens)
    B, S = tokens.shape
    if positions is None:
        positions = np.broadcast_to(np.arange(S), (3, B, S))
    positions = np.asarray(positions)
    theta = float(model["rope_theta"])
    section = list(model["rope_scaling"]["mrope_section"])
    idim = int(model["sa_config"]["indexer_head_dim"])
    put = lambda ang: tuple(jax.device_put(t, device) for t in cos_sin(ang))
    ang = [(put(mrope_angles(positions[:, b], int(model["head_dim"]), theta,
                             section)),
            put(angles(positions[0, b], idim, theta))) for b in range(B)]
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          for t in tokens]
    for i in range(int(model["num_hidden_layers"])):
        lp = layer_of(params, i, model, device)
        hs = [layer(lp, h, a_h, a_i, model, select)[0]
              for h, (a_h, a_i) in zip(hs, ang)]
        del lp
        jax.block_until_ready(hs)  # one layer's copy resident at a time
    fn = jax.device_put(params["final_norm"], device)
    lm = jax.device_put(params["lm_head"], device)
    out = []
    for b, h in enumerate(hs):
        rows = [np.asarray(head(fn, lm, h[r:r + ROW_BLOCK],
                                eps=float(model["rms_norm_eps"])))
                for r in range(0, S, ROW_BLOCK)]
        if targets is None:
            out.append(rows)
        else:
            out.append(float(mean_cross_entropy(
                jnp.asarray(np.concatenate(rows)),
                jax.device_put(jnp.asarray(targets[b]), device))))
    return out
