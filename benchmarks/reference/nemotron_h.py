"""The plain reference of the Nemotron-H block (``model_type: "nemotron_h"``;
NVIDIA-Nemotron-3-Super-120B-A12B): a layer is ONE sublayer, Mamba-2 (``M``),
LatentMoE experts (``E``) or NoPE attention (``*``), in the order
``hybrid_override_pattern`` gives, in jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, and nothing imported from ``picotron_tpu``.
The Mamba layer is the recurrence as it is written, one token after the
other (``lax.scan`` over ``t``) with B and C looked up by each head's group,
not the chunked matmul form the program prefills with; attention is a full
causal softmax; the experts run one after the other over every row.

What it computes (``x = RMSNorm(h)`` of one sequence, eps
``layer_norm_epsilon``; no bias anywhere but the conv's):

- ``h = E[tokens]``; every layer ``h <- h + sublayer(RMSNorm(h))``;
  ``logits = RMSNorm_f(h) W_head`` (untied);
- ``M``: ``[z | u | dt] = x W_in`` (``d_inner | d_inner + 2 G N | heads``,
  ``d_inner = mamba_num_heads * mamba_head_dim``, ``G = n_groups``, ``N =
  ssm_state_size``); ``u_t <- silu(b + sum_j w[:, j] u_{t - (conv_kernel - 1)
  + j})``, zeros before the sequence; ``[x_s | B | C] = u``, ``B``, ``C`` [G,
  N]; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``S_t = exp(dt_t
  A) S_{t-1} + dt_t x_t (x) B_t[g(h)]`` from ``S = 0``, ``g(h) = h // (heads
  / G)``; ``y_t = S_t C_t[g(h)] + D x_t``; ``y <- w * RMSNorm_group(y *
  silu(z))``, the mean square over each group's ``d_inner / G`` channels;
  ``W_out``;
- ``*``: ``q, k, v = x W_q, x W_k, x W_v`` (``num_attention_heads`` on
  ``num_key_value_heads`` of ``head_dim``), no rotation, causal softmax of ``q
  k^T / sqrt(head_dim)``, ``W_o``;
- ``E``: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok`` largest of ``s +
  b``, ties to the lower index (a stable sort); weights ``= s[chosen] / (sum
  + 1e-20) * routed_scaling_factor``; ``l = x W_down``; the sum over the
  chosen experts *held here* of ``w_e relu(l W1_e)^2 W2_e``, through ``W_up``,
  plus the shared expert ``relu(x Ws_1)^2 Ws_2`` on the stream.

Departures from the published description, the program's own and copied here
so that the two can agree:

- the share: ``n_routed_experts`` counts the experts held here, those from
  ``ep_rank * n_routed_experts`` on of a router ``n_routed_experts * ep_size``
  wide; what the absent experts would add is left out (before ``W_up``), and
  the vocabulary is the slice the tree holds;
- no position embedding in the attention layers: the family's forward reads
  neither ``rope_theta`` nor ``partial_rotary_factor``;
- ``n_group = topk_group = 1`` as published: no group limit is written here;
- the multi-token-prediction layer is not held;
- every matrix ``[in, out]``; the weights are the program's seeded random
  ones.

Parameters come from the system under test a layer at a time (``layer_of``:
the tree holds one stacked group a stretch of the pattern that repeats a unit
of distinct letters, ``stacking``, a sublayer's leaves named by its kind),
each matrix cast to float32 where it is used; the logits are taken in blocks
of rows, and every layer is waited for, so that the device's peak stays the
program's own.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 2048  # rows of logits at a time, each moved to the host
TAG = {"M": "m", "E": "e", "*": "a"}
LEAVES = {
    "M": ("m_norm", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
          "gate_norm", "out_proj"),
    "*": ("a_norm", "wq", "wk", "wv", "wo"),
    "E": ("e_norm", "router", "router_bias", "latent_down", "latent_up",
          "w1", "w2", "ws_up", "ws_down"),
}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


@jax.jit
def _relu2(x, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return jnp.square(jax.nn.relu(x @ w_up.astype(F32))) \
            @ w_down.astype(F32)


@jax.jit
def _project(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(F32)


@partial(jax.jit, static_argnames=("heads", "kv_heads"))
def _attention(x, wq, wk, wv, wo, *, heads: int, kv_heads: int):
    """Full causal softmax attention of one sequence ``x`` [S, H], GQA."""
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
        return o.reshape(S, -1) @ wo.astype(F32)


@partial(jax.jit, static_argnames=("heads", "d_head", "d_state", "groups",
                                   "eps"))
def _mamba(x, in_proj, conv_w, conv_b, dt_bias, A_log, D, gate_norm,
           out_proj, *, heads: int, d_head: int, d_state: int, groups: int,
           eps: float):
    """The Mamba-2 mixer on one sequence ``x`` [S, H], token by token."""
    S = x.shape[0]
    Di, GN = heads * d_head, groups * d_state
    K = conv_w.shape[-1]
    group_of = jnp.arange(heads) // (heads // groups)
    with jax.default_matmul_precision("highest"):
        proj = x @ in_proj.astype(F32)
        z, u, dt = proj[:, :Di], proj[:, Di:-heads], proj[:, -heads:]
        padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), F32), u])
        w = conv_w.astype(F32)
        u = jax.nn.silu(conv_b.astype(F32) + sum(
            padded[j:j + S] * w[:, j] for j in range(K)))
        xs = u[:, :Di].reshape(S, heads, d_head)
        Bm = u[:, Di:Di + GN].reshape(S, groups, d_state)
        Cm = u[:, Di + GN:].reshape(S, groups, d_state)
        dt = jax.nn.softplus(dt + dt_bias.astype(F32))  # [S, heads]
        A = -jnp.exp(A_log.astype(F32))

        def step(state, t):
            x_t, dt_t, B_t, C_t = t
            state = jnp.exp(dt_t * A)[:, None, None] * state \
                + (dt_t[:, None] * x_t)[:, :, None] * B_t[group_of][:, None]
            return state, jnp.sum(state * C_t[group_of][:, None], axis=-1)

        _, y = jax.lax.scan(step, jnp.zeros((heads, d_head, d_state), F32),
                            (xs, dt, Bm, Cm))
        y = y + D.astype(F32)[:, None] * xs
        y = y.reshape(S, Di) * jax.nn.silu(z)
        y = _rms_norm(y.reshape(S, groups, -1),
                      gate_norm.astype(F32).reshape(groups, -1), eps)
        return y.reshape(S, Di) @ out_proj.astype(F32)


@partial(jax.jit, static_argnames=("k", "scale"))
def _route(x, router, bias, *, k: int, scale: float):
    """(experts [S, k], weights [S, k]): the ``k`` largest of ``sigmoid(x
    W_r) + b``, ties to the lower index; the unbiased scores of those,
    normalised (``+ 1e-20``), times ``scale``."""
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ router.astype(F32))
    order = jnp.argsort(-(s + bias.astype(F32)), axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(s, order, axis=-1)
    return order, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale


def routed(lp, x, model: dict):
    """The routed experts held here, in the latent and back: [S, H]."""
    chosen, weights = _route(x, lp["router"], lp["router_bias"],
                             k=int(model["num_experts_per_tok"]),
                             scale=float(model["routed_scaling_factor"]))
    latent = _project(x, lp["latent_down"])
    held = int(model["n_routed_experts"])
    first = int(model.get("ep_rank", 0)) * held
    y = jnp.zeros_like(latent)
    for e in range(held):
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = y + w[:, None] * _relu2(latent, lp["w1"][e], lp["w2"][e])
    return _project(y, lp["latent_up"])


def experts(lp, x, model: dict):
    """The routed experts held here and the shared expert: [S, H]."""
    return routed(lp, x, model) + _relu2(x, lp["ws_up"], lp["ws_down"])


def layer(lp, h, model: dict, kind: str):
    """One layer of ``kind`` on one sequence, ``h`` [S, H] float32."""
    eps = float(model["layer_norm_epsilon"])
    x = _rms_norm(h, lp[TAG[kind] + "_norm"].astype(F32), eps)
    if kind == "M":
        return h + _mamba(
            x, lp["in_proj"], lp["conv_w"], lp["conv_b"], lp["dt_bias"],
            lp["A_log"], lp["D"], lp["gate_norm"], lp["out_proj"],
            heads=int(model["mamba_num_heads"]),
            d_head=int(model["mamba_head_dim"]),
            d_state=int(model["ssm_state_size"]),
            groups=int(model["n_groups"]), eps=eps)
    if kind == "*":
        return h + _attention(x, lp["wq"], lp["wk"], lp["wv"], lp["wo"],
                              heads=int(model["num_attention_heads"]),
                              kv_heads=int(model["num_key_value_heads"]))
    return h + experts(lp, x, model)


def stacking(pattern: str) -> list:
    """[(unit, first layer, repeats)] as the program's tree is grouped: the
    pattern cut, left to right, into stretches that repeat a unit of
    distinct letters, each the stretch that covers the most layers from
    where it starts (of equals the shorter unit)."""
    out, i = [], 0
    while i < len(pattern):
        best = (0, "", 0)
        for u in (1, 2, 3):
            unit = pattern[i:i + u]
            if len(set(unit)) < u or len(unit) < u:
                break
            r = 1
            while pattern[i + r * u:i + (r + 1) * u] == unit:
                r += 1
            if u * r > best[0]:
                best = (u * r, unit, r)
        out.append((best[1], i, best[2]))
        i += best[0]
    return out


def layer_of(params, i: int, model: dict, device):
    """(layer ``i`` of the system's tree, whole, on ``device``; its kind):
    the group of its stretch is named by the unit's kinds and the stretch's
    number, and holds a sublayer's leaves under that kind's names, one row
    a unit."""
    pattern = model["hybrid_override_pattern"]
    for g, (unit, first, repeats) in enumerate(stacking(pattern)):
        if first <= i < first + len(unit) * repeats:
            break
    group = params["".join(TAG[k] for k in unit) + f"_{g}"]
    row = (i - first) // len(unit)
    return jax.device_put({n: group[n][row] for n in LEAVES[pattern[i]]},
                          device), pattern[i]


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
        lp, kind = layer_of(params, i, model, device)
        hs = [layer(lp, h, model, kind) for h in hs]
        del lp
        jax.block_until_ready(hs)  # one layer's copy resident at a time
    fn = jax.device_put(params["final_norm"], device)
    lm = jax.device_put(params["lm_head"], device)
    out = []
    for b, h in enumerate(hs):
        rows = [np.asarray(head(fn, lm, h[r:r + ROW_BLOCK],
                                eps=float(model["layer_norm_epsilon"])))
                for r in range(0, S, ROW_BLOCK)]
        if targets is None:
            out.append(rows)
        else:
            out.append(float(mean_cross_entropy(
                jnp.asarray(np.concatenate(rows)),
                jax.device_put(jnp.asarray(targets[b]), device))))
    return out
