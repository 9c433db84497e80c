"""The plain reference of the Trinity block (``model_type: "afmoe"``): gated
GQA attention in every layer, over the last ``sliding_window`` keys with RoPE
in the ``sliding_attention`` layers and over everything with no position
embedding in the ``full_attention`` ones, a SwiGLU behind the first
``num_dense_layers`` layers and routed experts with a shared one behind the
others, in jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no ring, no batching, and nothing imported from
``picotron_tpu``. Attention is the causal (and windowed) softmax as it is
written, a block of query rows at a time against every key up to it.

What it computes (``x`` the normed stream of one sequence; ``N`` RMSNorm
with weight, eps ``rms_norm_eps``; no bias anywhere):

- ``h = sqrt(hidden_size) * E[tokens]`` (``mup_enabled``); a layer: ``h +=
  N2(Attn(N1(h)))``, then ``h += N4(MLP(N3(h)))``; ``logits = Nf(h)
  W_head``, untied;
- attention: ``q, k, v = x W_q, x W_k, x W_v`` (``num_attention_heads`` on
  ``num_key_value_heads`` of ``head_dim``); RMSNorm over each head's
  ``head_dim`` of ``q`` and of ``k``; in a sliding layer RoPE (``rope_theta``,
  halves paired) on both; softmax of ``q . k / sqrt(head_dim)`` over the keys
  ``s <= t`` (sliding layer: with ``t - s < sliding_window``); ``o *=
  sigmoid(x W_g)``; ``W_o``;
- MLP of the first ``num_dense_layers`` layers: ``(silu(x W_gate) * (x
  W_up)) W_down``. Of the others: ``s = sigmoid(x W_r)``; the
  ``num_experts_per_tok`` largest of ``s + b``, ties to the lower index (a
  stable sort); weights ``s[chosen] / (sum + 1e-20) * route_scale``; the sum
  over the chosen experts *held here* of ``w_e (silu(x W1_e) * (x W3_e))
  W2_e``, plus the shared expert of the same form.

Departures from the published description, the program's own and copied here
so that the two can agree:

- the share: ``num_experts`` counts the experts held here, those from
  ``ep_rank * num_experts`` on of a router ``num_experts * ep_size`` wide;
  what the absent experts would add is left out, and the vocabulary is the
  slice the tree holds;
- every matrix is held ``[in, out]``; the weights are the program's seeded
  random ones.

Parameters come from the system under test a layer at a time (``layer_of``:
the tree holds one stacked group a run of equal layers, named by what
follows the attention, what the attention sees and the run's number), each
matrix cast to float32 where it is used; the logits are taken in blocks of
rows, and every layer is waited for, so that the device's peak stays the
program's own.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 2048  # rows of logits at a time, each moved to the host
QUERY_BLOCK = 512  # query rows of attention at a time
WINDOW = "sliding_attention"


def _f32(lp, name):
    return lp[name].astype(F32)


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


@jax.jit
def _swiglu(x, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(x @ w_gate.astype(F32))
                * (x @ w_up.astype(F32))) @ w_down.astype(F32)


def _rope(x, theta: float):
    """RoPE on x [S, heads, D] at positions 0 .. S - 1, halves paired."""
    S, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=F32) / D)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "theta",
                                   "eps"))
def _attention(x, wq, wk, wv, wg, wo, q_norm, k_norm, *, heads: int,
               kv_heads: int, window: int, theta: float, eps: float):
    """The gated attention of one sequence ``x`` [S, H]: ``window`` > 0 a
    sliding layer (RoPE, the last ``window`` keys), 0 a full layer."""
    S = x.shape[0]
    with jax.default_matmul_precision("highest"):
        q = (x @ wq.astype(F32)).reshape(S, heads, -1)
        k = (x @ wk.astype(F32)).reshape(S, kv_heads, -1)
        v = (x @ wv.astype(F32)).reshape(S, kv_heads, -1)
        D = q.shape[-1]
        q = _rms_norm(q, q_norm.astype(F32), eps)
        k = _rms_norm(k, k_norm.astype(F32), eps)
        if window:
            q, k = _rope(q, theta), _rope(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        s_pos = jnp.arange(S)[None, :]
        outs = []
        for r in range(0, S, QUERY_BLOCK):
            t_pos = jnp.arange(r, min(r + QUERY_BLOCK, S))[:, None]
            seen = s_pos <= t_pos
            if window:
                seen &= t_pos - s_pos < window
            s = jnp.einsum("thd,shd->hts", q[r:r + QUERY_BLOCK], k) \
                / math.sqrt(D)
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            outs.append(jnp.einsum("hts,shd->thd", p, v))
        o = jnp.concatenate(outs).reshape(S, -1)
        return (o * jax.nn.sigmoid(x @ wg.astype(F32))) @ wo.astype(F32)


@partial(jax.jit, static_argnames=("k", "scale"))
def route(x, router, bias, *, k: int, scale: float):
    """(experts [S, k], weights [S, k]): the ``k`` largest of ``sigmoid(x
    W_r) + b``, ties to the lower index; the weights are the unbiased scores
    of those, normalised, times ``scale``."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ router.astype(F32))
    order = jnp.argsort(-(scores + bias.astype(F32)), axis=-1,
                        stable=True)[:, :k]
    w = jnp.take_along_axis(scores, order, axis=-1)
    return order, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale


def experts(lp, x, model: dict):
    """The routed experts held here and the shared expert: [S, H]."""
    chosen, weights = route(x, lp["router"], lp["router_bias"],
                            k=int(model["num_experts_per_tok"]),
                            scale=float(model["route_scale"]))
    y = _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    held = int(model["num_experts"])
    first = int(model.get("ep_rank", 0)) * held
    for e in range(held):
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = y + w[:, None] * _swiglu(x, lp["w1"][e], lp["w3"][e], lp["w2"][e])
    return y


def layer(lp, h, model: dict, kind: str):
    """One layer on one sequence, ``h`` [S, H] float32; ``kind`` its entry
    of ``layer_types``. An expert layer if its leaves hold a router."""
    eps = float(model["rms_norm_eps"])
    a = _attention(
        _rms_norm(h, _f32(lp, "attn_norm"), eps), lp["wq"], lp["wk"],
        lp["wv"], lp["wg"], lp["wo"], lp["q_norm"], lp["k_norm"],
        heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        window=int(model["sliding_window"]) if kind == WINDOW else 0,
        theta=float(model["rope_theta"]), eps=eps)
    h = h + _rms_norm(a, _f32(lp, "post_attn_norm"), eps)
    x = _rms_norm(h, _f32(lp, "mlp_norm"), eps)
    y = experts(lp, x, model) if "router" in lp else _swiglu(
        x, lp["w_gate"], lp["w_up"], lp["w_down"])
    return h + _rms_norm(y, _f32(lp, "post_mlp_norm"), eps)


def layer_of(params, i: int, model: dict, device):
    """Layer ``i`` of the system's tree, whole, on ``device``: the tree
    holds one stacked group a run of equal layers, named by the layer's MLP
    (``dense`` | ``moe``), its attention (``window`` | ``full``) and the
    run's number."""
    dense = int(model["num_dense_layers"])
    kinds = [("dense" if j < dense else "moe") + "_"
             + ("window" if t == WINDOW else "full")
             for j, t in enumerate(model["layer_types"])]
    run, first = 0, 0
    for j in range(1, i + 1):
        if kinds[j] != kinds[j - 1]:
            run, first = run + 1, j
    group = params[f"{kinds[i]}_{run}"]
    return jax.device_put(jax.tree.map(lambda v: v[i - first], group),
                          device)


@partial(jax.jit, static_argnames=("eps",))
def head(final_norm, lm_head, h, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, final_norm.astype(F32), eps) \
            @ lm_head.astype(F32)


@jax.jit
def mean_cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def forward_logits(params, tokens, model: dict, device=None):
    """Logits [B, S, V] (numpy float32, V the slice of the vocabulary the
    tree holds) of ``tokens`` [B, S]."""
    return np.stack([np.concatenate(rows) for rows in
                     _per_sequence(params, tokens, model, device, None)])


def loss(params, tokens, targets, model: dict, device=None) -> float:
    """Mean next-token cross-entropy over every position, over the sliced
    vocabulary: the mean of the sequences' means."""
    return float(np.mean(_per_sequence(params, tokens, model, device,
                                       np.asarray(targets))))


def _per_sequence(params, tokens, model, device, targets):
    device = device or jax.devices()[0]
    tokens = np.asarray(tokens)
    S = tokens.shape[1]
    mult = math.sqrt(model["hidden_size"]) if model.get("mup_enabled") else 1.0
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          * mult for t in tokens]
    for i in range(int(model["num_hidden_layers"])):
        lp = layer_of(params, i, model, device)
        hs = [layer(lp, h, model, model["layer_types"][i]) for h in hs]
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
