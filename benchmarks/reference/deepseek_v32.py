"""The plain reference of the DeepSeek-V3.2 block: multi-head latent attention
with the learned sparse selection, routed and shared experts, in jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, and nothing imported from ``picotron_tpu``.
It is *plain* MLA, not the latent-space form the program computes: every
token's ``k_nope`` and ``v`` are expanded per head through ``W_kvb``, the
scores are taken head by head against them and masked to the selected set.

What it computes (``x`` the normed stream of one sequence; RMSNorm unless
said):

- ``c_q = norm(x W_qa)``, ``q = c_q W_qb`` -> heads x (nope | rope), RoPE on
  the rope part; ``x W_kva`` -> (rank | rope), ``c_kv = norm(first)``, ``k_r =
  RoPE(last)`` shared by all heads; ``[k_nope | v] = c_kv W_kvb`` per head;
- the indexer: ``q^I = c_q W^I_qb``, ``k^I = LayerNorm(x W^I_k)`` with bias,
  RoPE on the first rope-dim of each, ``w = (x W^I_w) * heads^-0.5 *
  dim^-0.5``; ``I[t, s] = sum_h w[t, h] ReLU(q^I[t, h] . k^I[s])`` for ``s <=
  t``; the selected set of ``t`` is its ``min(index_topk, t + 1)`` best keys,
  exact, ties to the lower index (a stable sort);
- ``softmax((q_nope . k_nope + q_r . k_r) * scale)`` over the selected set,
  times ``v``, through ``W_o``; ``scale = (nope + rope)^-0.5 * m^2``, ``m =
  0.1 * mscale_all_dim * ln(factor) + 1``; YaRN's blended frequencies;
- layers past ``first_k_dense_replace``: ``s = sigmoid(x W_g)`` over the
  router's whole width; the choice on ``s + b`` (groups by the sum of their
  two best, ``topk_group`` groups, ``num_experts_per_tok`` experts), weights
  the unbiased ``s`` of the chosen normalised to 1 times
  ``routed_scaling_factor``; the sum over the chosen experts *held here*
  plus the shared expert. The leading layers: a SwiGLU.

Departures from the published description, the program's own and copied here
so that the two can agree:

- the share: ``n_routed_experts`` counts the experts held here, those from
  ``ep_rank * n_routed_experts`` on of a router ``n_routed_experts * ep_size``
  wide; what the absent experts would add is left out, and the vocabulary is
  the slice the tree holds;
- the indexer's Hadamard rotation of ``q^I`` and ``k^I`` is left out (it is
  orthogonal: their dot product is unchanged), and nothing is quantised to
  FP8;
- RoPE pairs adjacent elements in the attention and halves in the indexer,
  as the published inference code does (the catalog's row does not say);
- the head is never tied, there is no MTP module, and the weights are the
  program's seeded random ones, stored ``[in, out]``.

``forward_logits(..., select=False)`` switches the selection off: every
causal key is attended, which is what the model is while the context is
shorter than ``index_topk``.

Two controls, read by hand (``benchmarks/tests/control_dsv32.py``), compute
it in the nearest precision below bfloat16, the published model's own FP8
(E4M3, a power-of-two scale a block of 128): ``model["_fp8_indexer"]``
rounds ``q^I`` and ``k^I`` a vector at a time, as the published indexer
stores its keys; ``model["_fp8_weights"]`` rounds every matrix of a layer
but the router and the indexer's head weights, 128 x 128 at a time.

Parameters come from the system under test a layer at a time (``layer_of``),
each matrix cast to float32 where it is used; a long sequence's attention and
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
# rows attended at a time: bounds the [rows, heads, S] float32 scores; past
# LONG_SEQUENCE tokens the heads are expanded HEAD_GROUP at a time and the
# MLPs run ROW_BLOCK rows at a time
SCORE_ELEMS = 2 ** 26
LONG_SEQUENCE = 8192
HEAD_GROUP = 32
ROW_BLOCK = 2048


def yarn_inv_freq(dim: int, theta: float, scaling) -> np.ndarray:
    plain = 1.0 / (theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim))
    if not scaling:
        return plain
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def rope_angles(seq: int, dim: int, theta: float, scaling):
    """(cos, sin), each [seq, dim / 2] float32."""
    ang = np.arange(seq, dtype=np.float64)[:, None] \
        * yarn_inv_freq(dim, theta, scaling)[None, :]
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def softmax_scale(model: dict) -> float:
    scale = (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5
    rs = model.get("rope_scaling")
    if rs and float(rs["factor"]) > 1.0:
        m = 0.1 * float(rs.get("mscale_all_dim", 1.0)) \
            * math.log(float(rs["factor"])) + 1.0
        scale *= m * m
    return scale


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def _layer_norm(x, w, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LAYER_NORM_EPS) * w + b


def _rope_pairs(x, cos, sin):
    """Adjacent pairs (x[2i], x[2i+1]); x: [S, heads, D], cos/sin [S, D/2]."""
    a, b = x[..., 0::2], x[..., 1::2]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.stack([a * c - b * s, a * s + b * c], axis=-1).reshape(x.shape)


def _rope_halves(x, cos, sin):
    """Halves (x[i], x[i + D/2]); x: [S, heads, D], cos/sin [S, D/2]."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, a * s + b * c], axis=-1)


def _f32(lp, name):
    return lp[name].astype(F32)


FP8_MAX = 448.0  # the largest E4M3 value
FP8_BLOCK = 128
FP8_KEPT = ("router", "wi_w")  # published in bfloat16 / float32


def e4m3(x):
    """float32 ``x`` (|x| <= 448) rounded to the nearest E4M3 value, ties to
    even, in float32 arithmetic: the device need not know the type (a v5e's
    compiler may keep an 8-bit float in a wider one, and the round trip
    through ``float8_e4m3fn`` then rounds nothing). Three mantissa bits from
    2^-6 up, steps of 2^-9 below."""
    a = jnp.abs(x)
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    half = jnp.uint32(0x7FFFF) + ((bits >> 20) & jnp.uint32(1))
    normal = jax.lax.bitcast_convert_type(
        (bits + half) & jnp.uint32(0xFFF00000), F32)
    small = jnp.round(a * 512.0) / 512.0
    return jnp.sign(x) * jnp.minimum(
        jnp.where(a < 2.0 ** -6, small, normal), FP8_MAX)


def _fp8(x, axes):
    """``x`` rounded to E4M3 under one power-of-two scale over ``axes``."""
    amax = jnp.max(jnp.abs(x.astype(F32)), axis=axes, keepdims=True)
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.where(amax > 0, amax, 1.0)
                                       / FP8_MAX)))
    return (e4m3(x.astype(F32) / scale) * scale).astype(x.dtype)


@jax.jit
def fp8_blocks(w):
    """A matrix [.., in, out] rounded to E4M3, a scale a 128 x 128 block."""
    n, k = w.shape[-2:]
    pad = [(0, 0)] * (w.ndim - 2) + [(0, -n % FP8_BLOCK), (0, -k % FP8_BLOCK)]
    p = jnp.pad(w, pad)
    lead = p.shape[:-2]
    b = p.reshape(*lead, p.shape[-2] // FP8_BLOCK, FP8_BLOCK,
                  p.shape[-1] // FP8_BLOCK, FP8_BLOCK)
    return _fp8(b, (-3, -1)).reshape(p.shape)[..., :n, :k]


@partial(jax.jit, static_argnames=("dims", "eps", "fp8"))
def shared_parts(lp, x, cos, sin, *, dims, eps, fp8=False):
    """What all heads share, per token of one sequence: (c_q [S, q rank],
    c_kv [S, rank], k_r [S, rope], k^I [S, index dim])."""
    nh, dn, dr, dv, R, ih, idim = dims
    with jax.default_matmul_precision("highest"):
        c_q = _rms_norm(x @ _f32(lp, "wq_a"), _f32(lp, "q_norm"), eps)
        kv = x @ _f32(lp, "wkv_a")
        c_kv = _rms_norm(kv[:, :R], _f32(lp, "kv_norm"), eps)
        k_r = _rope_pairs(kv[:, None, R:], cos, sin)[:, 0]
        ki = _layer_norm(x @ _f32(lp, "wi_k"), _f32(lp, "ki_norm"),
                         _f32(lp, "ki_bias"))
        ki = jnp.concatenate([_rope_halves(ki[:, None, :dr], cos, sin)[:, 0],
                              ki[:, dr:]], axis=-1)
        return c_q, c_kv, k_r, _fp8(ki, -1) if fp8 else ki


@partial(jax.jit, static_argnames=("dims", "heads"))
def expand_heads(lp, c_kv, *, dims, heads):
    """(k_nope [S, n, nope], v [S, n, v]) of heads ``heads[0] ..
    heads[1]``: every token's compressed K/V expanded through ``W_kvb``."""
    nh, dn, dr, dv, R, ih, idim = dims
    with jax.default_matmul_precision("highest"):
        w_kvb = _f32(lp, "wkv_b").reshape(R, nh, dn + dv)[:, heads[0]:heads[1]]
        return (jnp.einsum("sc,chd->shd", c_kv, w_kvb[..., :dn]),
                jnp.einsum("sc,chd->shd", c_kv, w_kvb[..., dn:]))


@partial(jax.jit,
         static_argnames=("dims", "heads", "rows", "topk", "scale", "fp8"))
def attend_rows(lp, x, cos, sin, shared, expanded, r0, *, dims, heads,
                rows: int, topk: int, scale: float, fp8: bool = False):
    """What heads ``heads[0] .. heads[1]`` of queries ``r0 .. r0 + rows``
    add to the attention's output [rows, H] (their rows of ``W_o``
    applied), against the whole sequence; and the keys each query attended
    [rows, S]. ``topk`` 0: no selection, every causal key."""
    nh, dn, dr, dv, R, ih, idim = dims
    c_q, _, k_r, ki = shared
    k_nope, v = expanded
    h0, h1 = heads
    with jax.default_matmul_precision("highest"):
        S = k_nope.shape[0]
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, r0, rows, 0)
        cos_r, sin_r = cut(cos), cut(sin)
        w_qb = _f32(lp, "wq_b").reshape(-1, nh, dn + dr)[:, h0:h1]
        q = jnp.einsum("sc,chd->shd", cut(c_q), w_qb)
        q_nope, q_r = q[..., :dn], _rope_pairs(q[..., dn:], cos_r, sin_r)
        t = r0 + jnp.arange(rows)
        seen = jnp.arange(S)[None, :] <= t[:, None]  # [rows, S]
        if topk:
            qi = (cut(c_q) @ _f32(lp, "wi_q")).reshape(rows, ih, idim)
            qi = jnp.concatenate([_rope_halves(qi[..., :dr], cos_r, sin_r),
                                  qi[..., dr:]], axis=-1)
            qi = _fp8(qi, -1) if fp8 else qi
            w = (cut(x) @ _f32(lp, "wi_w")) * (ih ** -0.5 * idim ** -0.5)
            index = jnp.einsum("shd,td->sht", qi, ki)
            index = jnp.sum(jax.nn.relu(index) * w[:, :, None], axis=1)
            index = jnp.where(seen, index, -jnp.inf)
            # rank of every key among its query's: a stable sort puts the
            # lower index first among equals
            order = jnp.argsort(-index, axis=-1, stable=True)
            rank = jnp.argsort(order, axis=-1, stable=True)
            seen = seen & (rank < topk)
        s = (jnp.einsum("shd,thd->sht", q_nope, k_nope)
             + jnp.einsum("shr,tr->sht", q_r, k_r)) * scale
        s = jnp.where(seen[:, None, :], s, -jnp.inf)
        o = jnp.einsum("sht,thd->shd", jax.nn.softmax(s, axis=-1), v)
        w_o = _f32(lp, "wo").reshape(nh, dv, -1)[h0:h1]
        return jnp.einsum("shd,hdo->so", o, w_o), seen


def attention(lp, x, cos, sin, model: dict, select: bool = True):
    """([S, H]: the attention of one sequence, the keys each query attended
    as [rows, S] bool blocks when ``model`` asks for them under
    ``_keep_selected``, a by-hand reading). Heads are expanded
    ``HEAD_GROUP`` at a time once the sequence is long, and queries in
    blocks of rows, so that neither the expanded keys and values nor the
    scores outgrow the device."""
    nh = int(model["num_attention_heads"])
    dims = (nh, int(model["qk_nope_head_dim"]),
            int(model["qk_rope_head_dim"]), int(model["v_head_dim"]),
            int(model["kv_lora_rank"]), int(model["index_n_heads"]),
            int(model["index_head_dim"]))
    S = x.shape[0]
    fp8 = bool(model.get("_fp8_indexer"))
    shared = shared_parts(lp, x, cos, sin, dims=dims,
                          eps=float(model["rms_norm_eps"]), fp8=fp8)
    group = nh if S <= LONG_SEQUENCE else min(nh, HEAD_GROUP)
    rows = S
    while rows * group * S > SCORE_ELEMS and rows > 1:
        rows = (rows + 1) // 2
    topk = int(model["index_topk"]) if select else 0
    out, selected = 0.0, []
    for h0 in range(0, nh, group):
        heads = (h0, min(h0 + group, nh))
        expanded = expand_heads(lp, shared[1], dims=dims, heads=heads)
        blocks = []
        for r0 in range(0, S, rows):
            at = min(r0, S - rows)  # the last block steps back to stay whole
            o, seen = attend_rows(lp, x, cos, sin, shared, expanded, at,
                                  dims=dims, heads=heads, rows=rows,
                                  topk=topk, scale=softmax_scale(model),
                                  fp8=fp8)
            blocks.append(o[r0 - at:])
            if model.get("_keep_selected") and h0 == 0:
                selected.append(np.asarray(seen)[r0 - at:])
        del expanded
        out = out + jnp.concatenate(blocks)
    return out, selected


def route(scores, bias, model: dict):
    """(experts [S, k], weights [S, k]): group-limited choice on the biased
    scores, weights from the unbiased ones."""
    S, W = scores.shape
    n_group, keep = int(model["n_group"]), int(model["topk_group"])
    k = int(model["num_experts_per_tok"])
    choice = scores + bias
    groups = choice.reshape(S, n_group, W // n_group)
    group_score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)
    best = jnp.argsort(-group_score, axis=-1, stable=True)[:, :keep]
    kept = jnp.any(jnp.arange(n_group)[None, None, :] == best[:, :, None],
                   axis=1)
    choice = jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(S, W)
    experts = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, w / jnp.sum(w, axis=-1, keepdims=True) \
        * float(model["routed_scaling_factor"])


@jax.jit
def _swiglu(x, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        return (jax.nn.silu(x @ w_gate.astype(F32))
                * (x @ w_up.astype(F32))) @ w_down.astype(F32)


def swiglu(x, w_gate, w_up, w_down):
    """``(silu(x W_gate) * x W_up) W_down``, a long sequence ``ROW_BLOCK``
    rows at a time (the [S, width] products of the dense layer are the
    largest arrays of a forward pass)."""
    if x.shape[0] <= LONG_SEQUENCE:
        return _swiglu(x, w_gate, w_up, w_down)
    return jnp.concatenate([_swiglu(x[r:r + ROW_BLOCK], w_gate, w_up, w_down)
                            for r in range(0, x.shape[0], ROW_BLOCK)])


def experts(lp, x, model: dict):
    """The routed experts held here and the shared expert: [S, H]."""
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ _f32(lp, "router"))
        chosen, weights = route(scores, _f32(lp, "router_bias"), model)
    y = swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    first = int(model.get("ep_rank", 0)) * int(model["n_routed_experts"])
    for e in range(int(model["n_routed_experts"])):
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = y + w[:, None] * swiglu(x, lp["w1"][e], lp["w3"][e], lp["w2"][e])
    return y


def layer(lp, h, cos, sin, model: dict, select: bool = True):
    """One layer on one sequence, ``h`` [S, H] float32: an expert layer if
    its leaves hold a router, else a leading dense one."""
    eps = float(model["rms_norm_eps"])
    a, selected = attention(lp, _rms_norm(h, _f32(lp, "attn_norm"), eps),
                            cos, sin, model, select)
    h = h + a
    x = _rms_norm(h, _f32(lp, "mlp_norm"), eps)
    if "router" in lp:
        return h + experts(lp, x, model), selected
    return h + swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"]), selected


@partial(jax.jit, static_argnames=("eps",))
def head(final_norm, lm_head, h, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, final_norm.astype(F32), eps) \
            @ lm_head.astype(F32)


@jax.jit
def mean_cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def layer_of(params, i: int, model: dict, device):
    """Layer ``i`` of the system's tree (the leading dense layers are
    stacked under ``dense_layers``, the expert layers under ``layers``),
    whole, on ``device``."""
    k = int(model["first_k_dense_replace"])
    group, j = ("dense_layers", i) if i < k else ("layers", i - k)
    lp = jax.device_put(jax.tree.map(lambda v: v[j], params[group]), device)
    if model.get("_fp8_weights"):
        lp = {n: fp8_blocks(v) if v.ndim >= 2 and n not in FP8_KEPT else v
              for n, v in lp.items()}
    return lp


def forward_logits(params, tokens, model: dict, device=None, *,
                   select: bool = True):
    """Logits [B, S, V] (numpy float32, V the slice of the vocabulary the
    tree holds) of ``tokens`` [B, S]."""
    return np.stack([np.concatenate(rows) for rows in
                     _per_sequence(params, tokens, model, device, None,
                                   select)])


def loss(params, tokens, targets, model: dict, device=None) -> float:
    """Mean next-token cross-entropy over every position, over the sliced
    vocabulary: the mean of the sequences' means."""
    per = _per_sequence(params, tokens, model, device, np.asarray(targets),
                        True)
    return float(np.mean(per))


def _per_sequence(params, tokens, model, device, targets, select):
    device = device or jax.devices()[0]
    tokens = np.asarray(tokens)
    eps = float(model["rms_norm_eps"])
    S = tokens.shape[1]
    cos, sin = (jax.device_put(t, device) for t in rope_angles(
        S, int(model["qk_rope_head_dim"]), float(model["rope_theta"]),
        model.get("rope_scaling")))
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          for t in tokens]
    for i in range(int(model["num_hidden_layers"])):
        lp = layer_of(params, i, model, device)
        hs = [layer(lp, h, cos, sin, model, select)[0] for h in hs]
        del lp
        jax.block_until_ready(hs)  # one layer's copy resident at a time
    fn = jax.device_put(params["final_norm"], device)
    lm = jax.device_put(params["lm_head"], device)
    out = []
    block = 2048  # rows of logits at a time, each moved to the host
    for b, h in enumerate(hs):
        rows = [np.asarray(head(fn, lm, h[r:r + block], eps=eps))
                for r in range(0, S, block)]
        if targets is None:
            out.append(rows)
        else:
            out.append(float(mean_cross_entropy(
                jnp.asarray(np.concatenate(rows)),
                jax.device_put(jnp.asarray(targets[b]), device))))
    return out
