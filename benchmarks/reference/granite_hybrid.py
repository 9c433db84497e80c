"""The plain reference of the Granite-4.0-H block (``model_type:
"granitemoehybrid"``): Mamba-2 layers and NoPE attention layers in the order
``layer_types`` gives, each followed by routed experts and a shared MLP, in
jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, and nothing imported from ``picotron_tpu``.
The Mamba layer is the recurrence as it is written, one token after the
other (``lax.scan`` over ``t``), not the chunked matmul form the program
prefills with; attention is a full causal softmax.

What it computes (``x`` the normed stream of one sequence; RMSNorm, eps
``rms_norm_eps``; no bias anywhere but the conv's):

- ``h = E[tokens] * embedding_multiplier``; a layer: ``h +=
  residual_multiplier * mixer(norm(h))``, then ``h += residual_multiplier *
  (experts(norm(h)) + shared(norm(h)))``; ``logits = norm(h) E^T /
  logits_scaling``, the head tied to the embedding;
- attention layer: ``q, k, v = x W_q, x W_k, x W_v`` (``num_attention_heads``
  on ``num_key_value_heads`` of ``hidden_size / num_attention_heads``), no
  rotation, causal softmax of ``q k^T * attention_multiplier``, ``W_o``;
- Mamba-2 layer: ``[z | u | dt] = x W_in`` (``d_inner | d_inner + 2 d_state
  | heads``, ``d_inner = mamba_n_heads * mamba_d_head``); ``u_t <- silu(b +
  sum_j w[:, j] u_{t - (d_conv - 1) + j})``, zeros before the sequence; ``[x_s
  | B | C] = u``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``S_t
  = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`` from ``S = 0``; ``y_t = S_t C_t
  + D x_t``; ``y <- norm(y * silu(z))`` over all of ``d_inner``; ``W_out``;
- experts: ``l = x W_r``; the ``num_experts_per_tok`` largest logits, ties
  to the lower index (a stable sort); weights = softmax over those; the sum
  over the chosen experts *held here* of ``(silu(x W1_e) * (x W3_e)) W2_e``,
  plus the shared MLP of the same form.

Departures from the published description, the program's own and copied here
so that the two can agree:

- the share: ``num_local_experts`` counts the experts held here, those from
  ``ep_rank * num_local_experts`` on of a router ``num_local_experts *
  ep_size`` wide; what the absent experts would add is left out, and the
  vocabulary is the slice the tree holds;
- an expert's ``input_linear`` is held as its two halves ``W1`` and ``W3``
  (the same numbers, another layout), every matrix ``[in, out]``;
- the weights are the program's seeded random ones.

Parameters come from the system under test a layer at a time (``layer_of``:
the tree holds one stacked group a run of ``layer_types``, ``mamba_<i>`` or
``attention_<i>``), each matrix cast to float32 where it is used; the logits
are taken in blocks of rows, and every layer is waited for, so that the
device's peak stays the program's own.
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


@partial(jax.jit, static_argnames=("heads", "kv_heads", "scale"))
def _attention(x, wq, wk, wv, wo, *, heads: int, kv_heads: int,
               scale: float):
    """Full causal softmax attention of one sequence ``x`` [S, H], GQA."""
    S = x.shape[0]
    with jax.default_matmul_precision("highest"):
        q = (x @ wq.astype(F32)).reshape(S, heads, -1)
        k = (x @ wk.astype(F32)).reshape(S, kv_heads, -1)
        v = (x @ wv.astype(F32)).reshape(S, kv_heads, -1)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) * scale
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(S, -1) @ wo.astype(F32)


@partial(jax.jit, static_argnames=("heads", "d_head", "d_state", "eps"))
def _mamba(x, in_proj, conv_w, conv_b, dt_bias, A_log, D, gate_norm,
           out_proj, *, heads: int, d_head: int, d_state: int, eps: float):
    """The Mamba-2 mixer on one sequence ``x`` [S, H], token by token."""
    S = x.shape[0]
    Di = heads * d_head
    K = conv_w.shape[-1]
    with jax.default_matmul_precision("highest"):
        proj = x @ in_proj.astype(F32)
        z, u, dt = proj[:, :Di], proj[:, Di:-heads], proj[:, -heads:]
        padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
        w = conv_w.astype(F32)
        u = jax.nn.silu(conv_b.astype(F32) + sum(
            padded[j:j + S] * w[:, j] for j in range(K)))
        xs = u[:, :Di].reshape(S, heads, d_head)
        Bm, Cm = u[:, Di:Di + d_state], u[:, Di + d_state:]
        dt = jax.nn.softplus(dt + dt_bias.astype(F32))  # [S, heads]
        A = -jnp.exp(A_log.astype(F32))

        def step(state, t):
            x_t, dt_t, B_t, C_t = t
            state = jnp.exp(dt_t * A)[:, None, None] * state \
                + (dt_t[:, None] * x_t)[:, :, None] * B_t[None, None, :]
            return state, jnp.sum(state * C_t[None, None, :], axis=-1)

        _, y = jax.lax.scan(step, jnp.zeros((heads, d_head, d_state), F32),
                            (xs, dt, Bm, Cm))
        y = y + D.astype(F32)[:, None] * xs
        y = _rms_norm(y.reshape(S, Di) * jax.nn.silu(z),
                      gate_norm.astype(F32), eps)
        return y @ out_proj.astype(F32)


@partial(jax.jit, static_argnames=("k",))
def _route(x, router, *, k: int):
    """(experts [S, k], weights [S, k]): the ``k`` largest logits, ties to
    the lower index, and the softmax over those."""
    with jax.default_matmul_precision("highest"):
        logits = x @ router.astype(F32)
    order = jnp.argsort(-logits, axis=-1, stable=True)[:, :k]
    top = jnp.take_along_axis(logits, order, axis=-1)
    return order, jax.nn.softmax(top, axis=-1)


def experts(lp, x, model: dict):
    """The routed experts held here and the shared MLP: [S, H]."""
    chosen, weights = _route(x, lp["router"],
                             k=int(model["num_experts_per_tok"]))
    y = _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    held = int(model["num_local_experts"])
    first = int(model.get("ep_rank", 0)) * held
    for e in range(held):
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = y + w[:, None] * _swiglu(x, lp["w1"][e], lp["w3"][e], lp["w2"][e])
    return y


def layer(lp, h, model: dict):
    """One layer on one sequence, ``h`` [S, H] float32: a Mamba layer if its
    leaves hold an ``in_proj``, else an attention layer; then the experts."""
    eps = float(model["rms_norm_eps"])
    res = float(model["residual_multiplier"])
    x = _rms_norm(h, _f32(lp, "mixer_norm"), eps)
    if "in_proj" in lp:
        a = _mamba(x, lp["in_proj"], lp["conv_w"], lp["conv_b"],
                   lp["dt_bias"], lp["A_log"], lp["D"], lp["gate_norm"],
                   lp["out_proj"], heads=int(model["mamba_n_heads"]),
                   d_head=int(model["mamba_d_head"]),
                   d_state=int(model["mamba_d_state"]), eps=eps)
    else:
        a = _attention(x, lp["wq"], lp["wk"], lp["wv"], lp["wo"],
                       heads=int(model["num_attention_heads"]),
                       kv_heads=int(model["num_key_value_heads"]),
                       scale=float(model["attention_multiplier"]))
    h = h + res * a
    return h + res * experts(lp, _rms_norm(h, _f32(lp, "mlp_norm"), eps),
                             model)


def layer_of(params, i: int, model: dict, device):
    """Layer ``i`` of the system's tree, whole, on ``device``: the tree
    holds one stacked group a run of equal ``layer_types``, named by the
    run's kind and its number."""
    types = model["layer_types"]
    run, first = 0, 0
    for j in range(1, i + 1):
        if types[j] != types[j - 1]:
            run, first = run + 1, j
    group = params[f"{types[i]}_{run}"]
    return jax.device_put(jax.tree.map(lambda v: v[i - first], group),
                          device)


@partial(jax.jit, static_argnames=("eps", "scaling"))
def head(final_norm, embed, h, *, eps: float, scaling: float):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, final_norm.astype(F32), eps) \
            @ embed.astype(F32).T / scaling


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
    mult = float(model["embedding_multiplier"])
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          * mult for t in tokens]
    for i in range(int(model["num_hidden_layers"])):
        lp = layer_of(params, i, model, device)
        hs = [layer(lp, h, model) for h in hs]
        del lp
        jax.block_until_ready(hs)  # one layer's copy resident at a time
    fn = jax.device_put(params["final_norm"], device)
    emb = jax.device_put(params["embed"], device)
    out = []
    for b, h in enumerate(hs):
        rows = [np.asarray(head(fn, emb, h[r:r + ROW_BLOCK],
                                eps=float(model["rms_norm_eps"]),
                                scaling=float(model["logits_scaling"])))
                for r in range(0, S, ROW_BLOCK)]
        if targets is None:
            out.append(rows)
        else:
            out.append(float(mean_cross_entropy(
                jnp.asarray(np.concatenate(rows)),
                jax.device_put(jnp.asarray(targets[b]), device))))
    return out
