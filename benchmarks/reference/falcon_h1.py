"""The plain reference of the Falcon-H1 block (``model_type: "falcon_h1"``;
Falcon-H1-34B-Instruct): a parallel hybrid, a Mamba-2 mixer and GQA attention
side by side on one normed input in every layer, a SwiGLU behind them, a
published multiplier on every projection, in jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, and nothing imported from ``picotron_tpu``.
The Mamba mixer is the recurrence as it is written, one token after the other
(``lax.scan`` over ``t``) with B and C looked up by each head's group, not the
chunked matmul form the program prefills with; attention is a full causal
softmax with its own float32 angle table.

What it computes (``N(.)`` RMSNorm with weight, eps ``rms_norm_eps``, of one
sequence; no bias anywhere but the conv's; each multiplier applied where it
is written, none folded into a weight):

- ``h = E[tokens] * embedding_multiplier``; a layer: ``x = N_in(h)``, ``h +=
  ssm_out_multiplier * Mamba(x * ssm_in_multiplier) +
  attention_out_multiplier * Attn(x * attention_in_multiplier)``, then ``h +=
  MLP(N_ff(h))``; ``logits = (N_f(h) W_head) * lm_head_multiplier`` (untied);
- ``Attn(u)``: ``q = u W_q`` (``num_attention_heads`` of ``head_dim``), ``k =
  (u W_k) * key_multiplier``, ``v = u W_v`` (``num_key_value_heads``); RoPE
  over the whole head (pair ``i`` is columns ``i`` and ``i + head_dim / 2``,
  angle ``t * rope_theta^(-2 i / head_dim)``); causal softmax of ``q k^T /
  sqrt(head_dim)``; ``W_o``;
- ``Mamba(u)`` (``D = mamba_d_ssm = mamba_n_heads * mamba_d_head``, ``G =
  mamba_n_groups``, ``N = mamba_d_state``): ``[z | x | B | C | dt] = (u W_in)
  * mup`` (``D | D | G N | G N | heads`` columns, ``mup`` holding
  ``ssm_multipliers[0..4]`` over those ranges); ``[x | B | C]_t <- silu(b +
  sum_j w[:, j] [x | B | C]_{t - (mamba_d_conv - 1) + j})``, zeros before the
  sequence; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; ``S_t =
  exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g(i)]`` from ``S = 0``, ``g(i) = i //
  (heads / G)``; ``y_t = S_t C_t[g(i)] + D x_t``; ``y <- w * RMSNorm_group(y *
  silu(z))``, the mean square over each group's ``D / G`` channels; ``W_out``;
- ``MLP(u) = ((u W_up) * silu((u W_gate) * mlp_multipliers[0])) W_down *
  mlp_multipliers[1]``.

What the published ``config.json`` keys do not state, and the published
modeling code's conventions settle (the configuration file lists each under
``assumed``): the order of ``W_in``'s columns and the ranges of ``mup``;
``ssm_in_multiplier`` before ``W_in``; ``key_multiplier`` on ``k`` before the
rotation; ``mlp_multipliers`` = (gate, down); the gated norm's groups =
``mamba_n_groups``; attention and an MLP in every layer. Every matrix ``[in,
out]``; the weights are the program's seeded random ones.

Parameters come from the system under test a layer at a time (``layer_of``),
each matrix cast to float32 where it is used. The head is 5.35 GB in float32
beside the program's resident tree: it is taken in blocks of columns, each
cast, multiplied and moved to the host, so that the device's peak stays the
program's own.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
COL_BLOCK = 8192  # columns of the head at a time, each moved to the host
LEAVES = ("input_norm", "mlp_norm", "wq", "wk", "wv", "wo", "in_proj",
          "conv_w", "conv_b", "dt_bias", "A_log", "D", "gate_norm",
          "out_proj", "w_gate", "w_up", "w_down")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


def _rotate(x, theta: float):
    """RoPE on ``x`` [S, heads, d] at positions 0 .. S - 1."""
    S, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / d)
    ang = jnp.arange(S, dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "theta", "key_mult"))
def _attention(u, wq, wk, wv, wo, *, heads: int, kv_heads: int, theta: float,
               key_mult: float):
    """Full causal softmax attention of one sequence ``u`` [S, H], GQA."""
    S = u.shape[0]
    with jax.default_matmul_precision("highest"):
        q = (u @ wq.astype(F32)).reshape(S, heads, -1)
        k = ((u @ wk.astype(F32)) * key_mult).reshape(S, kv_heads, -1)
        v = (u @ wv.astype(F32)).reshape(S, kv_heads, -1)
        q, k = _rotate(q, theta), _rotate(k, theta)
        k = jnp.repeat(k, heads // kv_heads, axis=1)
        v = jnp.repeat(v, heads // kv_heads, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) * q.shape[-1] ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
        o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
        return o.reshape(S, -1) @ wo.astype(F32)


@partial(jax.jit, static_argnames=("heads", "d_head", "d_state", "groups",
                                   "eps", "mults"))
def _mamba(u, in_proj, conv_w, conv_b, dt_bias, A_log, D, gate_norm,
           out_proj, *, heads: int, d_head: int, d_state: int, groups: int,
           eps: float, mults: tuple):
    """(the Mamba-2 mixer's output on one sequence ``u`` [S, H], token by
    token; the state ``S`` [heads, d_head, d_state] behind the last token);
    ``mults`` the five ``ssm_multipliers`` (z, x, B, C, dt)."""
    S = u.shape[0]
    Di, GN = heads * d_head, groups * d_state
    K = conv_w.shape[-1]
    group_of = jnp.arange(heads) // (heads // groups)
    with jax.default_matmul_precision("highest"):
        proj = u @ in_proj.astype(F32)
        z = proj[:, :Di] * mults[0]
        xBC = jnp.concatenate([proj[:, Di:2 * Di] * mults[1],
                               proj[:, 2 * Di:2 * Di + GN] * mults[2],
                               proj[:, 2 * Di + GN:2 * Di + 2 * GN]
                               * mults[3]], axis=-1)
        dt = proj[:, 2 * Di + 2 * GN:] * mults[4]
        padded = jnp.concatenate([jnp.zeros((K - 1, xBC.shape[1]), F32), xBC])
        w = conv_w.astype(F32)
        xBC = jax.nn.silu(conv_b.astype(F32) + sum(
            padded[j:j + S] * w[:, j] for j in range(K)))
        xs = xBC[:, :Di].reshape(S, heads, d_head)
        Bm = xBC[:, Di:Di + GN].reshape(S, groups, d_state)
        Cm = xBC[:, Di + GN:].reshape(S, groups, d_state)
        dt = jax.nn.softplus(dt + dt_bias.astype(F32))  # [S, heads]
        A = -jnp.exp(A_log.astype(F32))

        def step(state, t):
            x_t, dt_t, B_t, C_t = t
            state = jnp.exp(dt_t * A)[:, None, None] * state \
                + (dt_t[:, None] * x_t)[:, :, None] * B_t[group_of][:, None]
            return state, jnp.sum(state * C_t[group_of][:, None], axis=-1)

        last, y = jax.lax.scan(
            step, jnp.zeros((heads, d_head, d_state), F32), (xs, dt, Bm, Cm))
        y = y + D.astype(F32)[:, None] * xs
        y = y.reshape(S, Di) * jax.nn.silu(z)
        y = _rms_norm(y.reshape(S, groups, -1),
                      gate_norm.astype(F32).reshape(groups, -1), eps)
        return y.reshape(S, Di) @ out_proj.astype(F32), last


@partial(jax.jit, static_argnames=("gate_mult", "down_mult"))
def _mlp(x, w_gate, w_up, w_down, *, gate_mult: float, down_mult: float):
    with jax.default_matmul_precision("highest"):
        y = (x @ w_up.astype(F32)) * jax.nn.silu(
            (x @ w_gate.astype(F32)) * gate_mult)
        return (y @ w_down.astype(F32)) * down_mult


def layer(lp, h, model: dict):
    """One layer on one sequence, ``h`` [S, H] float32."""
    return layer_and_state(lp, h, model)[0]


def layer_and_state(lp, h, model: dict):
    """(``layer``'s output, the mixer's state behind the last token)."""
    eps = float(model["rms_norm_eps"])
    x = _rms_norm(h, lp["input_norm"].astype(F32), eps)
    ssm, state = _mamba(
        x * float(model["ssm_in_multiplier"]), lp["in_proj"], lp["conv_w"],
        lp["conv_b"], lp["dt_bias"], lp["A_log"], lp["D"], lp["gate_norm"],
        lp["out_proj"], heads=int(model["mamba_n_heads"]),
        d_head=int(model["mamba_d_head"]), d_state=int(model["mamba_d_state"]),
        groups=int(model["mamba_n_groups"]), eps=eps,
        mults=tuple(float(s) for s in model["ssm_multipliers"]))
    attn = _attention(
        x * float(model["attention_in_multiplier"]), lp["wq"], lp["wk"],
        lp["wv"], lp["wo"], heads=int(model["num_attention_heads"]),
        kv_heads=int(model["num_key_value_heads"]),
        theta=float(model["rope_theta"]),
        key_mult=float(model["key_multiplier"]))
    h = h + float(model["ssm_out_multiplier"]) * ssm \
        + float(model["attention_out_multiplier"]) * attn
    gate_mult, down_mult = (float(s) for s in model["mlp_multipliers"])
    return h + _mlp(_rms_norm(h, lp["mlp_norm"].astype(F32), eps),
                    lp["w_gate"], lp["w_up"], lp["w_down"],
                    gate_mult=gate_mult, down_mult=down_mult), state


def layer_of(params, i: int, device):
    """Layer ``i`` of the system's tree (one stacked group, ``layers``),
    whole, on ``device``."""
    return jax.device_put({n: params["layers"][n][i] for n in LEAVES},
                          device)


@partial(jax.jit, static_argnames=("eps",))
def _final_norm(final_norm, h, *, eps: float):
    return _rms_norm(h, final_norm.astype(F32), eps)


@partial(jax.jit, static_argnames=("mult",))
def _head_block(x, w, *, mult: float):
    with jax.default_matmul_precision("highest"):
        return (x @ w.astype(F32)) * mult


def head(params, h, model: dict, device):
    """Logits [S, V] (numpy float32) of the last hidden rows ``h`` [S, H]:
    the head a block of columns at a time."""
    x = _final_norm(jax.device_put(params["final_norm"], device), h,
                    eps=float(model["rms_norm_eps"]))
    lm = params["lm_head"]
    return np.concatenate([np.asarray(_head_block(
        x, jax.device_put(lm[:, c:c + COL_BLOCK], device),
        mult=float(model["lm_head_multiplier"])))
        for c in range(0, lm.shape[1], COL_BLOCK)], axis=-1)


@jax.jit
def mean_cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def forward_logits(params, tokens, model: dict, device=None):
    """Logits [B, S, V] (numpy float32) of ``tokens`` [B, S]."""
    return np.stack(_per_sequence(params, tokens, model, device, None))


def forward_logits_and_state(params, tokens, model: dict, device=None):
    """(``forward_logits``, the float32 state of every layer's mixer behind
    each sequence's last token [B, layers, heads, d_head, d_state]), of one
    forward."""
    states = []
    logits = _per_sequence(params, tokens, model, device, None, states)
    return np.stack(logits), np.stack(states, axis=1)


def loss(params, tokens, targets, model: dict, device=None) -> float:
    """Mean next-token cross-entropy over every position: the mean of the
    sequences' means."""
    return float(np.mean(_per_sequence(params, tokens, model, device,
                                       np.asarray(targets))))


def _per_sequence(params, tokens, model, device, targets, states=None):
    """``states``, a list: every layer's final states [B, ...] appended."""
    device = device or jax.devices()[0]
    scale = float(model["embedding_multiplier"])
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          * scale for t in np.asarray(tokens)]
    for i in range(int(model["num_hidden_layers"])):
        lp = layer_of(params, i, device)
        hs, last = zip(*(layer_and_state(lp, h, model) for h in hs))
        if states is not None:
            states.append(np.stack([np.asarray(s) for s in last]))
        del lp, last
        jax.block_until_ready(hs)  # one layer's copy resident at a time
    out = []
    for b, h in enumerate(hs):
        logits = head(params, h, model, device)
        out.append(logits if targets is None else float(mean_cross_entropy(
            jnp.asarray(logits), jnp.asarray(targets[b]))))
    return out
