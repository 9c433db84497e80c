"""The plain reference of the MiMo-V2 block (``model_type: "mimo_v2"``,
MiMo-V2.5's language model): GQA attention in every layer, full or sliding
by ``hybrid_layer_pattern`` (0 | 1) with K/V heads, head widths and a RoPE
base of the layer's own kind, keys wider than values, a third of each head
rotated, a learned sink in the sliding layers' softmax; a SwiGLU or routed
experts with no shared one behind it by ``moe_layer_freq`` (0 | 1), in
jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no ring, no batching, and nothing imported from
``picotron_tpu``. Attention is the causal (and windowed) softmax as it is
written, a block of query rows at a time against every key up to it; the
sink is a column of logits joined to the scores before the softmax and
dropped after it.

What it computes (``x`` the normed stream of one sequence; ``N`` RMSNorm
with weight, eps ``rms_norm_eps``; no bias in any projection):

- ``h = E[tokens]``; a layer: ``h += Attn(N1(h))``, then ``h += MLP(N2(h))``;
  ``logits = Nf(h) W_head``, untied;
- attention of a full layer: ``q, k = x W_q, x W_k`` (``num_attention_heads``
  on ``num_key_value_heads`` of ``head_dim``), ``v = attention_value_scale *
  x W_v`` (heads of ``v_head_dim``); RoPE (``rope_theta``, halves paired) on
  the leading ``int(head_dim * partial_rotary_factor)`` dimensions of every
  head of ``q`` and ``k``; softmax of ``q . k / sqrt(head_dim)`` over the
  keys ``s <= t``; ``W_o``. Of a sliding layer: the same under the ``swa_*``
  keys (``swa_num_attention_heads``, ``swa_num_key_value_heads``,
  ``swa_head_dim``, ``swa_v_head_dim``, ``swa_rope_theta``), over the keys
  with ``t - s < sliding_window``, and with the layer's sink ``b_h`` a query
  head among the logits: ``p = softmax([z, b_h])[:-1]``;
- MLP where ``moe_layer_freq`` is 0: ``(silu(x W_gate) * (x W_up)) W_down``.
  Where it is 1: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` largest
  of ``s + b``, ties to the lower index (a stable sort); weights ``s[chosen]
  / (sum + 1e-20)``, times ``routed_scaling_factor`` (null: 1); the sum over
  the chosen experts *held here* of ``w_e (silu(x W1_e) * (x W3_e)) W2_e``.

Departures from the published description, the program's own and copied here
so that the two can agree:

- the share: ``n_routed_experts`` counts the experts held here, those from
  ``ep_rank * n_routed_experts`` on of a router ``n_routed_experts *
  ep_size`` wide; what the absent experts would add is left out, and the
  vocabulary is the slice the tree holds;
- every matrix is held ``[in, out]`` (the checkpoint's fused q/k/v matrix is
  three leaves); the weights are the program's seeded random ones;
- the three multi-token prediction layers and the vision and audio towers
  are not held.

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


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(F32)


@jax.jit
def _swiglu(x, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(x @ w_gate.astype(F32))
                * (x @ w_up.astype(F32))) @ w_down.astype(F32)


def _rope(x, theta: float, rot: int):
    """RoPE on the leading ``rot`` dimensions of x [S, heads, D] at
    positions 0 .. S - 1, halves of ``rot`` paired; the rest untouched."""
    S = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    r = x[..., :rot]
    r1, r2 = r[..., :rot // 2], r[..., rot // 2:]
    turned = r * cos + jnp.concatenate([-r2, r1], -1) * sin
    return jnp.concatenate([turned, x[..., rot:]], -1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "window", "theta",
                                   "rot", "value_scale"))
def _attention(x, wq, wk, wv, wo, sink, *, heads: int, kv_heads: int,
               window: int, theta: float, rot: int, value_scale: float):
    """The attention of one sequence ``x`` [S, H]: ``window`` > 0 a sliding
    layer (the last ``window`` keys; ``sink`` [heads] among the logits), 0 a
    full layer (``sink`` None)."""
    S = x.shape[0]
    with jax.default_matmul_precision("highest"):
        q = (x @ wq.astype(F32)).reshape(S, heads, -1)
        k = (x @ wk.astype(F32)).reshape(S, kv_heads, -1)
        v = (value_scale * (x @ wv.astype(F32))).reshape(S, kv_heads, -1)
        D = q.shape[-1]
        q, k = _rope(q, theta, rot), _rope(k, theta, rot)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        s_pos = jnp.arange(S)[None, :]
        outs = []
        for r in range(0, S, QUERY_BLOCK):
            t_pos = jnp.arange(r, min(r + QUERY_BLOCK, S))[:, None]
            seen = s_pos <= t_pos
            if window:
                seen &= t_pos - s_pos < window
            z = jnp.einsum("thd,shd->hts", q[r:r + QUERY_BLOCK], k) \
                / math.sqrt(D)
            z = jnp.where(seen[None], z, -jnp.inf)
            if sink is not None:
                # a column of logits joined to the softmax and dropped
                col = jnp.broadcast_to(sink.astype(F32)[:, None, None],
                                       z.shape[:2] + (1,))
                p = jax.nn.softmax(jnp.concatenate([z, col], -1),
                                   axis=-1)[..., :-1]
            else:
                p = jax.nn.softmax(z, axis=-1)
            outs.append(jnp.einsum("hts,shd->thd", p, v))
        return jnp.concatenate(outs).reshape(S, -1) @ wo.astype(F32)


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
    """The routed experts held here: [S, H]. No shared expert."""
    chosen, weights = route(
        x, lp["router"], lp["router_bias"],
        k=int(model["num_experts_per_tok"]),
        scale=float(model.get("routed_scaling_factor") or 1.0))
    held = int(model["n_routed_experts"])
    first = int(model.get("ep_rank", 0)) * held
    y = jnp.zeros_like(x)
    for e in range(held):
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = y + w[:, None] * _swiglu(x, lp["w1"][e], lp["w3"][e], lp["w2"][e])
    return y


def layer(lp, h, model: dict, sliding: bool):
    """One layer on one sequence, ``h`` [S, H] float32; ``sliding`` its
    entry of ``hybrid_layer_pattern``. An expert layer if its leaves hold a
    router."""
    eps = float(model["rms_norm_eps"])
    pre = "swa_" if sliding else ""
    head_dim = int(model[pre + "head_dim"])
    a = _attention(
        _rms_norm(h, lp["attn_norm"], eps), lp["wq"], lp["wk"], lp["wv"],
        lp["wo"], lp["sink"] if sliding else None,
        heads=int(model[pre + "num_attention_heads"]),
        kv_heads=int(model[pre + "num_key_value_heads"]),
        window=int(model["sliding_window"]) if sliding else 0,
        theta=float(model["swa_rope_theta" if sliding else "rope_theta"]),
        rot=int(head_dim * float(model["partial_rotary_factor"])),
        value_scale=float(model["attention_value_scale"]))
    h = h + a
    x = _rms_norm(h, lp["mlp_norm"], eps)
    return h + (experts(lp, x, model) if "router" in lp else _swiglu(
        x, lp["w_gate"], lp["w_up"], lp["w_down"]))


def layer_of(params, i: int, model: dict, device):
    """Layer ``i`` of the system's tree, whole, on ``device``: the tree
    holds one stacked group a run of equal layers, named by the layer's MLP
    (``dense`` | ``moe``), its attention (``window`` | ``full``) and the
    run's number."""
    kinds = [("moe" if f else "dense") + "_" + ("window" if p else "full")
             for f, p in zip(model["moe_layer_freq"],
                             model["hybrid_layer_pattern"])]
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
        return _rms_norm(h, final_norm, eps) @ lm_head.astype(F32)


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
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          for t in tokens]
    for i in range(int(model["num_hidden_layers"])):
        lp = layer_of(params, i, model, device)
        hs = [layer(lp, h, model, bool(model["hybrid_layer_pattern"][i]))
              for h in hs]
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
