"""The plain reference of the MiniCPM-SALA block (``model_type:
"minicpm_sala"``): lightning linear-attention layers and block-sparse NoPE
attention layers (InfLLM-v2) in the order ``mixer_types`` gives, a SwiGLU
behind each, in jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, and nothing imported from ``picotron_tpu``.
The lightning layer is the recurrence as it is written, one token after the
other (``lax.scan`` over ``t``), not the chunked matmul form the program
prefills with. The sparse layer scores every compressed key, ranks the key
blocks with a stable sort and attends with a full masked softmax over the
sequence, a block of query rows at a time so that it fits.

What it computes (``x`` the normed stream of one sequence; RMSNorm, eps
``rms_norm_eps``; no bias anywhere):

- ``h = scale_emb * E[tokens]``; a layer: ``h += r * mixer(norm(h))``, then
  ``h += r * SwiGLU(norm(h))``, ``r = scale_depth / sqrt(total_layers)``
  (the whole model's depth); ``logits = norm(h) W_head / (hidden_size /
  dim_model_base)``, the head untied;
- ``lightning-attn``: ``q, k, v = x W_q, x W_k, x W_v`` in ``lightning_nh``
  heads of ``lightning_head_dim``; RMSNorm over each head's width of ``q`` and
  of ``k``; RoPE (``rope_theta``, halves rotated) on both at the token's
  position; per head ``S_t = exp(-slope) S_{t-1} + v_t (x) k_t`` from ``S =
  0``, ``o_t = S_t q_t / sqrt(d)``; RMSNorm over all heads of ``o``; ``o *=
  sigmoid(x W_g)``; ``W_o``. ``slope`` is the layer's own leaf;
- ``minicpm4`` (``sparse_config``: ``st = kernel_stride``, ``kernel_size = 2
  st``, ``bs = block_size``): ``q, k, v`` in ``num_attention_heads`` on
  ``num_key_value_heads`` of ``hidden_size / num_attention_heads``, no
  rotation; ``kc_c = mean(k[st c : st c + 2 st])`` per kv head, visible at
  ``t`` when ``st c + 2 st - 1 <= t``; per query head ``p = softmax_c(q .
  kc_c / sqrt(d))`` over the visible ``c``, summed over a kv head's query
  heads; block ``b``'s score the largest of that over the visible windows
  ``c`` with ``st c < bs (b + 1)`` and ``st c + 2 st > bs b``; +inf for block
  0 .. ``init_blocks`` - 1 and the ``window_size / bs`` blocks up to ``t //
  bs``; the ``topk`` blocks of highest score among those ``<= t // bs`` are
  kept, ties to the lower index (a stable sort); each query head's softmax of
  ``q . k / sqrt(d)`` over the tokens ``s <= t`` of the kept blocks. A query
  at ``t < dense_len`` attends over every ``s <= t``. ``o *= sigmoid(x
  W_g)``; ``W_o``.

Departures from the published description, the program's own and copied here
so that the two can agree (the configuration's ``assumed`` and
``departures`` have each with its reason): the dense rule goes by the
query's position, not by the whole call's length; the forced blocks count
among the ``topk``; the held layers are ``first_layer`` onward of
``total_layers``; the weights are the program's seeded random ones.

Parameters come from the system under test a layer at a time (``layer_of``:
the tree holds one stacked group a run of ``mixer_types``, ``sparse_<i>`` or
``lightning_<i>``), each matrix cast to float32 where it is used; the logits
are taken in blocks of rows, and every layer is waited for, so that the
device's peak stays the program's own.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 2048  # rows of logits at a time, each moved to the host
QUERY_BLOCK = 128  # query rows of a sparse layer at a time
KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w


@jax.jit
def _swiglu(x, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(x @ w_gate.astype(F32))
                * (x @ w_up.astype(F32))) @ w_down.astype(F32)


def _rope(x, theta: float):
    """``x`` [S, heads, d] rotated at positions 0 .. S - 1, halves paired."""
    S, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1), F32)[:, None]
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1), F32)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@partial(jax.jit, static_argnames=("heads", "eps", "theta"))
def _lightning(x, wq, wk, wv, wg, wo, q_norm, k_norm, out_norm, slope, *,
               heads: int, eps: float, theta: float):
    """The lightning mixer on one sequence ``x`` [S, H], token by token."""
    S = x.shape[0]
    with jax.default_matmul_precision("highest"):
        q = (x @ wq.astype(F32)).reshape(S, heads, -1)
        k = (x @ wk.astype(F32)).reshape(S, heads, -1)
        v = (x @ wv.astype(F32)).reshape(S, heads, -1)
        d = q.shape[-1]
        q = _rope(_rms_norm(q, q_norm.astype(F32), eps), theta)
        k = _rope(_rms_norm(k, k_norm.astype(F32), eps), theta)
        lam = jnp.exp(-slope.astype(F32))[:, None, None]

        def step(state, t):
            q_t, k_t, v_t = t
            state = lam * state + v_t[:, :, None] * k_t[:, None, :]
            return state, jnp.sum(state * q_t[:, None, :], axis=-1)

        _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), F32), (q, k, v))
        o = _rms_norm((o / math.sqrt(d)).reshape(S, -1),
                      out_norm.astype(F32), eps)
        o = o * jax.nn.sigmoid(x @ wg.astype(F32))
        return o @ wo.astype(F32)


@partial(jax.jit, static_argnames=("heads", "kv_heads"))
def _qkv(x, wq, wk, wv, *, heads: int, kv_heads: int):
    S = x.shape[0]
    with jax.default_matmul_precision("highest"):
        return ((x @ wq.astype(F32)).reshape(S, heads, -1),
                (x @ wk.astype(F32)).reshape(S, kv_heads, -1),
                (x @ wv.astype(F32)).reshape(S, kv_heads, -1))


@partial(jax.jit, static_argnames=("st",))
def compressed_keys(k, *, st: int):
    """[windows, kv heads, d]: ``mean(k[st c : st c + 2 st])`` for every
    window that fits the sequence ``k`` [S, kv heads, d]."""
    n = max((k.shape[0] - 2 * st) // st + 1, 0)
    rows = st * jnp.arange(n)[:, None] + jnp.arange(2 * st)[None, :]
    return jnp.mean(k[rows], axis=1)


@partial(jax.jit, static_argnames=("st", "bs", "init", "window", "topk",
                                   "dense_len", "blocks"))
def kept_blocks(q, t, kc, *, st: int, bs: int, init: int, window: int,
                topk: int, dense_len: int, blocks: int):
    """[rows, kv heads, blocks] bool: the key blocks the queries ``q``
    [rows, heads, d] at positions ``t`` [rows] attend over, from the
    compressed keys ``kc`` [windows, kv heads, d]."""
    R, nq, d = q.shape
    C, nkv, _ = kc.shape
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("tgqd,cgd->tgqc", q.reshape(R, nkv, nq // nkv, d),
                       kc) / math.sqrt(d)
    c = jnp.arange(C)
    vis = (st * c[None, :] + 2 * st - 1 <= t[:, None])[:, None, None, :]
    s = jnp.where(vis, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(vis, jnp.exp(s - jnp.where(vis, top, 0.0)), 0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.sum(p / jnp.where(total > 0, total, 1.0), axis=2)  # [R, g, C]
    b = jnp.arange(blocks)
    touch = (st * c[None, :] < bs * (b[:, None] + 1)) \
        & (st * c[None, :] + 2 * st > bs * b[:, None])  # [blocks, C]
    score = jnp.max(jnp.where(touch[None, None] & vis[:, :, 0, None, :],
                              p[:, :, None, :], 0.0), axis=-1)
    cur = (t // bs)[:, None, None]
    forced = (b[None, None, :] < init) \
        | (b[None, None, :] > cur - window // bs)
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(b[None, None, :] <= cur, score, -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)[..., :topk]
    kept = jnp.zeros(score.shape, bool).at[
        jnp.arange(R)[:, None, None], jnp.arange(nkv)[None, :, None],
        order].set(True) & (score > -jnp.inf)
    dense = (t < dense_len)[:, None, None]
    return jnp.where(dense, b[None, None, :] <= cur, kept)


@partial(jax.jit, static_argnames=("bs",))
def _attend(q, t, k, v, kept, *, bs: int):
    """[rows, heads x d]: each query's softmax over the tokens ``s <= t`` of
    its kv head's kept blocks."""
    R, nq, d = q.shape
    S, nkv, _ = k.shape
    s_tok = jnp.arange(S)
    on = jnp.take(kept, s_tok // bs, axis=-1) \
        & (s_tok[None, None, :] <= t[:, None, None])  # [R, g, S]
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("tgqd,sgd->tgqs", q.reshape(R, nkv, nq // nkv, d),
                       k) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(on[:, :, None, :], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("tgqs,sgd->tgqd", p, v).reshape(R, -1)


@jax.jit
def _gate_out(a, x, wg, wo):
    with jax.default_matmul_precision("highest"):
        return (a * jax.nn.sigmoid(x @ wg.astype(F32))) @ wo.astype(F32)


def sparse_sizes(model: dict) -> dict:
    sc = model["sparse_config"]
    return dict(st=int(sc["kernel_stride"]), bs=int(sc["block_size"]),
                init=int(sc["init_blocks"]), window=int(sc["window_size"]),
                topk=int(sc["topk"]), dense_len=int(sc["dense_len"]))


def sparse_mixer(lp, x, model: dict, kept_out: list | None = None):
    """The block-sparse attention mixer on one sequence ``x`` [S, H]; the
    kept blocks of every query are appended to ``kept_out`` when given."""
    S = x.shape[0]
    sz = sparse_sizes(model)
    q, k, v = _qkv(x, lp["wq"], lp["wk"], lp["wv"],
                   heads=int(model["num_attention_heads"]),
                   kv_heads=int(model["num_key_value_heads"]))
    kc = compressed_keys(k, st=sz["st"])
    blocks = -(-S // sz["bs"])
    rows = []
    for r0 in range(0, S, QUERY_BLOCK):
        t = jnp.arange(r0, min(r0 + QUERY_BLOCK, S))
        if kc.shape[0]:
            kept = kept_blocks(q[r0:r0 + QUERY_BLOCK], t, kc, blocks=blocks,
                               **sz)
        else:  # no window fits: every block up to the query's own
            kept = jnp.broadcast_to(
                (jnp.arange(blocks)[None, :] <= (t // sz["bs"])[:, None]
                 )[:, None, :], (t.shape[0], k.shape[1], blocks))
        if kept_out is not None:
            kept_out.append(np.asarray(kept))
        rows.append(_attend(q[r0:r0 + QUERY_BLOCK], t, k, v, kept,
                            bs=sz["bs"]))
    return _gate_out(jnp.concatenate(rows), x, lp["wg"], lp["wo"])


def layer(lp, h, model: dict, kept_out: list | None = None):
    """One layer on one sequence, ``h`` [S, H] float32: a lightning layer if
    its leaves hold a ``slope``, else a sparse layer; then the SwiGLU."""
    eps = float(model["rms_norm_eps"])
    depth = int(model.get("total_layers") or model["num_hidden_layers"])
    res = float(model["scale_depth"]) / math.sqrt(depth)
    x = _rms_norm(h, lp["mixer_norm"].astype(F32), eps)
    if "slope" in lp:
        a = _lightning(x, lp["wq"], lp["wk"], lp["wv"], lp["wg"], lp["wo"],
                       lp["q_norm"], lp["k_norm"], lp["out_norm"],
                       lp["slope"], heads=int(model["lightning_nh"]),
                       eps=eps, theta=float(model["rope_theta"]))
    else:
        a = sparse_mixer(lp, x, model, kept_out)
    h = h + res * a
    x = _rms_norm(h, lp["mlp_norm"].astype(F32), eps)
    return h + res * _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def layer_of(params, i: int, model: dict, device):
    """Layer ``i`` of the system's tree, whole, on ``device``: the tree
    holds one stacked group a run of equal ``mixer_types``, named by the
    run's kind and its number."""
    types = model["mixer_types"]
    run, first = 0, 0
    for j in range(1, i + 1):
        if types[j] != types[j - 1]:
            run, first = run + 1, j
    group = params[f"{KINDS[types[i]]}_{run}"]
    return jax.device_put(jax.tree.map(lambda v: v[i - first], group),
                          device)


@partial(jax.jit, static_argnames=("eps", "scaling"))
def head(final_norm, lm_head, h, *, eps: float, scaling: float):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, final_norm.astype(F32), eps) \
            @ lm_head.astype(F32) / scaling


@jax.jit
def mean_cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def forward_logits(params, tokens, model: dict, device=None):
    """Logits [B, S, V] (numpy float32) of ``tokens`` [B, S]."""
    return np.stack([np.concatenate(rows) for rows in
                     _per_sequence(params, tokens, model, device, None)])


def loss(params, tokens, targets, model: dict, device=None) -> float:
    """Mean next-token cross-entropy over every position: the mean of the
    sequences' means."""
    return float(np.mean(_per_sequence(params, tokens, model, device,
                                       np.asarray(targets))))


def _per_sequence(params, tokens, model, device, targets):
    device = device or jax.devices()[0]
    tokens = np.asarray(tokens)
    S = tokens.shape[1]
    mult = float(model["scale_emb"])
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          * mult for t in tokens]
    for i in range(int(model["num_hidden_layers"])):
        lp = layer_of(params, i, model, device)
        hs = [layer(lp, h, model) for h in hs]
        del lp
        jax.block_until_ready(hs)  # one layer's copy resident at a time
    fn = jax.device_put(params["final_norm"], device)
    lm = jax.device_put(params["lm_head"], device)
    scaling = float(model["hidden_size"]) / float(model["dim_model_base"])
    out = []
    for b, h in enumerate(hs):
        rows = [np.asarray(head(fn, lm, h[r:r + ROW_BLOCK],
                                eps=float(model["rms_norm_eps"]),
                                scaling=scaling))
                for r in range(0, S, ROW_BLOCK)]
        if targets is None:
            out.append(rows)
        else:
            out.append(float(mean_cross_entropy(
                jnp.asarray(np.concatenate(rows)),
                jax.device_put(jnp.asarray(targets[b]), device))))
    return out
