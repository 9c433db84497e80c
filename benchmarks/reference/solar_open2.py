"""The plain reference of the Solar Open 2 block (``model_type:
"solar_open2"``; Solar-Open2-250B): Kimi-delta-attention (KDA) layers and
gated NoPE GQA layers (the layers ``gqa_layers`` lists), each followed by
routed experts and a shared one, in jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, and nothing imported from ``picotron_tpu``.
The KDA layer is the recurrence as it is written, one token after the other
(``lax.scan`` over ``t``: decay, read along ``k``, write, read out), not the
chunked form with its triangular solve that the program prefills with; its
convolutions are sums over four shifted copies; attention is a full causal
softmax; the experts run one after the other over every row.

What it computes (``x = RMSNorm(h)`` of one sequence, eps ``rms_norm_eps``;
no bias anywhere):

- ``h = E[tokens]``; every layer ``h <- h + mixer(RMSNorm_1(h))``, then ``h
  <- h + experts(RMSNorm_2(h))``; ``logits = RMSNorm_f(h) W_head`` (untied);
- KDA layer (``linear_attn_config``: ``num_heads`` heads of ``head_dim``,
  ``short_conv_kernel_size`` taps): ``q' = conv_q(x W_q)``, ``k' = conv_k(x
  W_k)``, ``v = conv_v(x W_v)``, ``c_t = silu(sum_j w[:, j] u_{t - (taps - 1)
  + j})``, zeros before the sequence; a head at a time ``q = q' / ||q'|| *
  head_dim^-0.5``, ``k = k' / ||k'||`` (eps 1e-6 under the root); ``g =
  -exp(A_log[h]) * softplus((x W_fa) W_fb + dt_bias)``, ``a = exp(g)``; ``b =
  sigmoid(x W_b)``, doubled under ``kda_allow_neg_eigval``; from ``S = 0``,
  ``S[h]`` [keys, values]: ``S' = Diag(a_t) S_{t-1}``, ``S_t = S' + b_t k_t
  (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``; ``y = w_o * RMSNorm_head(o_t) *
  sigmoid((x W_ga) W_gb)``; ``W_out``;
- GQA layer: ``q, k, v = x W_q, x W_k, x W_v`` (``num_attention_heads`` on
  ``num_key_value_heads`` of ``head_dim``), no rotation (``use_rope``
  false), causal softmax of ``q k^T / sqrt(head_dim)``, times ``sigmoid(x
  W_g)`` an entry for an entry (``use_gqa_gate``), ``W_o``;
- experts: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` largest of ``s
  + bias``, ties to the lower index (a stable sort); weights ``= s[chosen] /
  (sum + 1e-20) * routed_scaling_factor``; the sum over the chosen experts
  *held here* of ``w_e (silu(x W1_e) * (x W3_e)) W2_e``, plus the shared
  expert of the same form.

Departures from the published description, the program's own and copied here
so that the two can agree:

- the share: ``n_routed_experts`` counts the experts held here, those from
  ``ep_rank * n_routed_experts`` on of a router ``n_routed_experts * ep_size``
  wide; what the absent experts would add is left out, and the vocabulary is
  the slice the tree holds;
- the tree holds ``W_q``, ``W_k`` and ``W_v`` of a KDA layer side by side as
  ``wqkv`` and the three convolutions' taps as one ``conv_w`` over all their
  channels (depthwise, so the same numbers in another layout); they are cut
  apart here;
- ``kda_use_full_proj`` true reads one matrix (``w_f``, ``w_g``) where the
  published false reads the pair through a rank of ``head_dim``;
- no layer reads ``intermediate_size``, ``rope_theta`` or
  ``partial_rotary_factor`` (``first_k_dense_replace`` 0, ``use_rope`` false);
- every matrix ``[in, out]``; the weights are the program's seeded random
  ones.

Parameters come from the system under test a layer at a time (``layer_of``:
the tree holds one stacked group a run of equal mixers, ``kda_<i>`` or
``gqa_<i>``), each matrix cast to float32 where it is used; the logits are
taken in blocks of rows, and every layer is waited for, so that the device's
peak stays the program's own.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 2048  # rows of logits at a time, each moved to the host


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


@partial(jax.jit, static_argnames=("heads", "kv_heads"))
def _attention(x, wq, wk, wv, wg, wo, *, heads: int, kv_heads: int):
    """Full causal softmax attention of one sequence ``x`` [S, H], GQA, no
    rotation; ``wg`` None: no gate."""
    S = x.shape[0]
    with jax.default_matmul_precision("highest"):
        q = (x @ wq.astype(F32)).reshape(S, heads, -1)
        k = (x @ wk.astype(F32)).reshape(S, kv_heads, -1)
        v = (x @ wv.astype(F32)).reshape(S, kv_heads, -1)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) * q.shape[-1] ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
        o = o.reshape(S, -1)
        if wg is not None:
            o = o * jax.nn.sigmoid(x @ wg.astype(F32))
        return o @ wo.astype(F32)


def _short_conv(u, w):
    """``silu(sum_j w[:, j] u_{t - (taps - 1) + j})`` of ``u`` [S, C], zeros
    before the sequence: a sum over ``taps`` shifted copies."""
    S, taps = u.shape[0], w.shape[-1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u])
    return jax.nn.silu(sum(padded[j:j + S] * w[:, j] for j in range(taps)))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@partial(jax.jit, static_argnames=("heads", "eps", "neg_eigval"))
def _kda(x, wq, wk, wv, conv_q, conv_k, conv_v, decay, A_log, dt_bias, w_b,
         gate, o_norm, wo, *, heads: int, eps: float, neg_eigval: bool):
    """The KDA mixer on one sequence ``x`` [S, H], token by token.
    ``decay``/``gate``: the matrices ``x`` goes through on its way to a
    head's width, one after the other (two, or one)."""
    S = x.shape[0]
    with jax.default_matmul_precision("highest"):
        def through(ws):
            y = x
            for w in ws:
                y = y @ w.astype(F32)
            return y

        split = lambda a: a.reshape(S, heads, -1)
        q = split(_short_conv(x @ wq.astype(F32), conv_q.astype(F32)))
        k = split(_short_conv(x @ wk.astype(F32), conv_k.astype(F32)))
        v = split(_short_conv(x @ wv.astype(F32), conv_v.astype(F32)))
        q = _l2(q) * q.shape[-1] ** -0.5
        k = _l2(k)
        g = -jnp.exp(A_log.astype(F32))[:, None] * split(
            jax.nn.softplus(through(decay) + dt_bias.astype(F32)))
        b = jax.nn.sigmoid(x @ w_b.astype(F32)) * (2.0 if neg_eigval else 1.0)

        def step(state, t):  # state [heads, keys, values]
            q_t, k_t, v_t, g_t, b_t = t
            state = jnp.exp(g_t)[:, :, None] * state  # the decay first
            was = jnp.einsum("hkv,hk->hv", state, k_t)  # read along k
            state = state + b_t[:, None, None] * k_t[:, :, None] \
                * (v_t - was)[:, None, :]  # write the difference
            return state, jnp.einsum("hkv,hk->hv", state, q_t)  # then read

        _, o = jax.lax.scan(
            step, jnp.zeros((heads, k.shape[-1], v.shape[-1]), F32),
            (q, k, v, g, b))
        y = _rms_norm(o, o_norm.astype(F32), eps) \
            * jax.nn.sigmoid(split(through(gate)))
        return y.reshape(S, -1) @ wo.astype(F32)


@partial(jax.jit, static_argnames=("k", "scale"))
def _route(x, router, bias, *, k: int, scale: float):
    """(experts [S, k], weights [S, k]): the ``k`` largest of ``sigmoid(x
    W_r) + bias``, ties to the lower index; the unbiased scores of those,
    normalised (``+ 1e-20``), times ``scale``."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ router.astype(F32))
    order = jnp.argsort(-(s + bias.astype(F32)), axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(s, order, axis=-1)
    return order, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale


def experts(lp, x, model: dict):
    """The routed experts held here and the shared expert: [S, H]."""
    chosen, weights = _route(x, lp["router"], lp["router_bias"],
                             k=int(model["num_experts_per_tok"]),
                             scale=float(model["routed_scaling_factor"]))
    y = _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    held = int(model["n_routed_experts"])
    first = int(model.get("ep_rank", 0)) * held
    for e in range(held):
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = y + w[:, None] * _swiglu(x, lp["w1"][e], lp["w3"][e], lp["w2"][e])
    return y


def kda(lp, x, model: dict):
    """A KDA layer's mixer from the tree's leaves: ``wqkv`` and ``conv_w``
    cut into the three projections and the three convolutions."""
    la = model["linear_attn_config"]
    D = int(la["num_heads"]) * int(la["head_dim"])
    cut = lambda a, axis: [jax.lax.slice_in_dim(a, i * D, (i + 1) * D,
                                                axis=axis) for i in range(3)]
    full = bool(model.get("kda_use_full_proj", False))
    return _kda(x, *cut(lp["wqkv"], 1), *cut(lp["conv_w"], 0),
                (lp["w_f"],) if full else (lp["w_fa"], lp["w_fb"]),
                lp["A_log"], lp["dt_bias"], lp["w_b"],
                (lp["w_g"],) if full else (lp["w_ga"], lp["w_gb"]),
                lp["o_norm"], lp["wo"], heads=int(la["num_heads"]),
                eps=float(model["rms_norm_eps"]),
                neg_eigval=bool(model["kda_allow_neg_eigval"]))


def layer(lp, h, model: dict):
    """One layer on one sequence, ``h`` [S, H] float32: a KDA layer if its
    leaves hold a ``wqkv``, else a GQA layer; then the experts."""
    eps = float(model["rms_norm_eps"])
    x = _rms_norm(h, _f32(lp, "mixer_norm"), eps)
    if "wqkv" in lp:
        a = kda(lp, x, model)
    else:
        a = _attention(x, lp["wq"], lp["wk"], lp["wv"], lp.get("wg"),
                       lp["wo"], heads=int(model["num_attention_heads"]),
                       kv_heads=int(model["num_key_value_heads"]))
    h = h + a
    return h + experts(lp, _rms_norm(h, _f32(lp, "mlp_norm"), eps), model)


def layer_of(params, i: int, model: dict, device):
    """Layer ``i`` of the system's tree, whole, on ``device``: the tree
    holds one stacked group a run of equal mixers, named by the run's kind
    and its number."""
    gqa = set(model["gqa_layers"])
    kinds = ["gqa" if j in gqa else "kda" for j in range(i + 1)]
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
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          for t in tokens]
    for i in range(int(model["num_hidden_layers"])):
        lp = layer_of(params, i, model, device)
        hs = [layer(lp, h, model) for h in hs]
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
