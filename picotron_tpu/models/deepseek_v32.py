"""The DeepSeek-V3.2 block as pure functions over a parameter pytree: latent
attention (MLA) with a learned sparse selection (DSA), routed and shared
experts. Serving path only (``Config.validate`` refuses the rest by name).

The equations (``x`` the normed residual stream; RMSNorm unless said):

- queries: ``c_q = norm(x W_qa)``; ``q = c_q W_qb`` -> heads x (nope | rope),
  RoPE on the rope part;
- latent K/V: ``x W_kva`` -> (kv_lora_rank | rope); ``c_kv = norm(first)``,
  ``k_r = RoPE(last)``, one for all heads. The cache holds ``[c_kv | k_r]`` in one row;
- indexer: ``q^I = c_q W^I_qb`` -> index heads x index dim, ``k^I =
  LayerNorm(x W^I_k)`` (with bias), RoPE on the first ``qk_rope_head_dim`` of
  each, ``w = (x W^I_w) * heads^-0.5 * dim^-0.5`` in float32. Key ``s``
  scores ``I[t, s] = sum_h w[t, h] * ReLU(q^I[t, h] . k^I[s])`` for ``s <=
  t``; the selected set is the ``min(index_topk, t + 1)`` keys of largest
  score, exact, ties to the lower index. The cache holds ``k^I``;
- attention over the selected rows, in the latent space (``W_kvb`` viewed
  ``[rank, heads, nope + v]``): ``q~[h] = q_nope[h] . W_kvb[:, h, :nope]``,
  ``a[t, h, s] = (q~[t, h] . c_kv[s] + q_r[t, h] . k_r[s]) * scale``, softmax
  in float32, ``o[h] = (sum_s p c_kv[s]) . W_kvb[:, h, nope:]``, ``x +=
  concat(o) W_o``; ``scale = (nope + rope)^-0.5 * mscale^2`` (ops/rope.py);
- experts (behind the ``first_k_dense_replace`` dense SwiGLU layers): ``s =
  sigmoid(x W_g)`` in float32 over the router's whole width
  (``n_routed_experts * ep_size``); the choice is made on ``s + b``: groups
  scored by the sum of their two best, the best ``topk_group`` groups kept,
  the best ``num_experts_per_tok`` experts among them; their weights are the
  unbiased ``s``, normalised to sum 1, times ``routed_scaling_factor``. This
  chip holds ``n_routed_experts`` of the experts (``ep_rank * n_routed_experts``
  onward) and adds their part and the shared expert's; what the absent
  experts would add is left out. No token is ever dropped.

Departures from the published inference code: the indexer's Hadamard rotation
of ``q^I``, ``k^I`` is left out (orthogonal: the dot product is unchanged) and
its keys are kept in the model's dtype, not FP8.

RoPE pairing as published: adjacent pairs in the attention
(``apply_rope_interleaved``), halves in the indexer (``apply_rope``).

A prefill (a chunk of one slot, or a whole sequence without a cache) selects
by mask and attends masked under a running softmax over the live key blocks
(``_attend_selected``: 2,048 different keys for each of its queries is no row
gather). A decode step (``S == 1`` over the slots' cache) takes the chosen
keys' row indices (``select_rows``), GATHERS those rows of ``ckv`` and
attends over them alone (``_attend_rows``): ``min(context, index_topk)`` rows
of 1,280 B a slot and layer where the walk reads the context.

Every layer function returns, beside the updated cache leaves, what it
counted (``STATS``: an int32 vector in the order of ``STAT_NAMES``; the
programs return a row a layer, summed over a block's steps, the engine adds
the rows up on the host and the batcher adds them to the registry;
docs/OBSERVABILITY.md). A layer's largest count is the keys scored by a
one-shot prefill, S^2 / 2: under 2^31 while S < 65,536, far past where its
[S, S] scores fit a chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.inference import kv_cache
from picotron_tpu.models import STATS, served_whole, support
from picotron_tpu.models import experts as expert_share
from picotron_tpu.models.experts import swiglu as _swiglu
from picotron_tpu.models.llama import (  # noqa: F401 - the seam's shared parts
    embed_lookup,
    head_logits,
    param_bytes,
)
from picotron_tpu.ops.attention import NEG_INF
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.select import (
    KEY_BLOCK,  # keys a block of the indexer's and the attend's walks
    gather_rows,
    index_scores,
    key_block as _key_block,
    key_blocks as _key_blocks,
    layer_norm as _layer_norm,
    live_blocks as _live_blocks,
    select_keys,
    select_rows,
)
from picotron_tpu.ops.rope import (
    apply_rope,
    apply_rope_interleaved,
    precompute_rope,
    yarn_mscale,
)

# what a layer counts, in the order of the vector (under ``STATS``); the
# last is the latent rows a query's attend read (``min(context, index_topk)``
# through a decode step's gather, the context through a masked walk)
STAT_NAMES = expert_share.STAT_NAMES + (
    "dsa_keys_selected", "dsa_keys_scored", "dsa_rows_attended")

# queries attended at a time: bounds the [B, S, heads, keys] scores of a key
# block (``ops/select.py::KEY_BLOCK`` keys, the indexer's blocks)
QUERY_BLOCK = 128
LEAVES = kv_cache.LATENT_LEAVES  # the latent cache's, beside "lengths"

# what the block cannot do yet, and why (``support.refuse``)
WHY = {
    **support.LLAMA_ONLY,
    "training": "no backward through the selection and the expert share",
    "tp": "the latent cache has no head axis to shard and the block holds no "
          "tp collectives; its share of a layer is ep_size/ep_rank",
    "dp": "the latent cache has no slot axis over 'dp'",
    "paged": "the latent cache is contiguous only (paged_kv.py pages K/V "
             "heads); set kv_layout: 'contiguous'",
    "kv_int8": "the latent cache is stored in the model's dtype",
    "speculation": "there is no verify program for this block and the MTP "
                   "module is cut with the depth",
    "flash": "the flash-decode kernel reads K/V heads, not latent rows",
}


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name so that nothing runs the Llama block under this model's
    name (``Config.validate`` calls it)."""
    m = cfg.model
    support.refuse(cfg, for_training, WHY)
    support.positive(
        m, "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim",
        "index_topk", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "moe_intermediate_size", "n_group",
        "topk_group", "ep_size")
    support.check(m, (
        m.qk_rope_head_dim % 2 or m.index_head_dim < m.qk_rope_head_dim,
        f"qk_rope_head_dim ({m.qk_rope_head_dim}) must be even and fit "
        f"index_head_dim ({m.index_head_dim})"))
    support.ep_share(m)
    width = router_width(m)
    support.check(
        m,
        (width % m.n_group or m.topk_group > m.n_group
         or m.num_experts_per_tok > m.topk_group * (width // m.n_group)
         or width // m.n_group < 2,
         f"the router's width {width} (n_routed_experts x ep_size) must "
         f"split into n_group {m.n_group} groups of at least 2, with "
         f"topk_group {m.topk_group} <= n_group and num_experts_per_tok "
         f"{m.num_experts_per_tok} experts inside the kept groups"),
        (not 0 <= m.first_k_dense_replace <= m.num_hidden_layers,
         f"first_k_dense_replace {m.first_k_dense_replace} outside [0, "
         f"num_hidden_layers {m.num_hidden_layers}]"))
    support.pinned(m, scoring_func="sigmoid", topk_method="noaux_tc",
                   norm_topk_prob=True, moe_layer_freq=1,
                   num_nextn_predict_layers=0)
    rs = m.rope_scaling
    if rs is not None and rs.get("type", rs.get("rope_type")) != "yarn":
        raise ValueError(
            f"{support.who(m)} implements rope_scaling type 'yarn' only (got "
            f"{rs!r})")


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #


def router_width(m: ModelConfig) -> int:
    return m.n_routed_experts * m.ep_size


def _attn_shapes(m: ModelConfig) -> dict:
    H, nh = m.hidden_size, m.num_attention_heads
    return {
        "wq_a": (H, m.q_lora_rank),
        "wq_b": (m.q_lora_rank, nh * (m.qk_nope_head_dim
                                      + m.qk_rope_head_dim)),
        "wkv_a": (H, m.kv_lora_rank + m.qk_rope_head_dim),
        "wkv_b": (m.kv_lora_rank, nh * (m.qk_nope_head_dim + m.v_head_dim)),
        "wo": (nh * m.v_head_dim, H),
        "wi_q": (m.q_lora_rank, m.index_n_heads * m.index_head_dim),
        "wi_k": (H, m.index_head_dim),
        "wi_w": (H, m.index_n_heads),
    }


def _group_shapes(m: ModelConfig, dense: bool) -> dict:
    """Matmul leaves of one layer of a group, (in, out) like every weight
    here; the routed experts lead with the experts held."""
    H = m.hidden_size
    shapes = _attn_shapes(m)
    if dense:
        I = m.intermediate_size
        shapes.update(w_gate=(H, I), w_up=(H, I), w_down=(I, H))
        return shapes
    E, I = m.n_routed_experts, m.moe_intermediate_size
    Is = m.n_shared_experts * I
    shapes.update(router=(H, router_width(m)),
                  w1=(E, H, I), w3=(E, H, I), w2=(E, I, H),
                  ws_gate=(H, Is), ws_up=(H, Is), ws_down=(Is, H))
    return shapes


UNSLICED = expert_share.UNSLICED


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    layers)]: the leading dense layers, then the expert layers. The engine
    scans one group after the other over one cache."""
    k = m.first_k_dense_replace
    groups = [("dense_layers", dense_layer, k),
              ("layers", moe_layer, m.num_hidden_layers - k)]
    return [g for g in groups if g[2] > 0]


# Seeded weights are drawn so that the block's mechanisms matter to a
# comparison of logits. With every matrix U(+-sqrt(1 / fan_in)) the
# attention's output is a mean of ~1400 rows, a hundredth of the residual
# stream beside an MLP ten times as loud: neither which keys were selected
# nor a cache row that is wrong moves a logit by more than bf16's own
# rounding does, and a check of logits guards neither. ``wo`` four times
# wider makes the attention as loud as the MLP. The softmax stays as flat as
# the draw gives it (its logits spread by 0.6): a sharper one (``wq_b``
# wider) turns bf16's rounding of a logit into the weight's, and the sound
# program's own error grew faster than any fault's (PERF.md, PR 28). The
# routed experts' ``w2`` half as wide: a held expert chosen in bf16 and not
# in float32 (a tie of the router broken by rounding, which is no fault)
# then moves a logit by a third of the check's limit and not by two thirds.
INIT_GAIN = {"wo": 4.0, "w2": 0.5}


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key``: linear weights U(+-gain *
    sqrt(1 / fan_in)) (``INIT_GAIN``, else 1) drawn in the model's dtype (no
    float32 copy of an 8 GB tree is ever made), embedding N(0, 1), norm
    weights ones; the router's correction bias and the indexer's LayerNorm
    bias are drawn small, so that they matter to a comparison."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("deepseek_v32 is served on one stage (pp_size 1)")
    dt = jnp.dtype(m.dtype)
    H, V = m.hidden_size, m.vocab_size

    def uniform(k, shape, fan_in, gain=1.0):
        bound = gain * math.sqrt(1.0 / fan_in)
        return jax.random.uniform(k, shape, dt, -bound, bound)

    def group(gkey, n: int, dense: bool) -> dict:
        ones = lambda w: jnp.ones((n, w), dt)
        out = {"attn_norm": ones(H), "q_norm": ones(m.q_lora_rank),
               "kv_norm": ones(m.kv_lora_rank), "mlp_norm": ones(H),
               "ki_norm": ones(m.index_head_dim)}
        shapes = sorted(_group_shapes(m, dense).items())
        for i, (name, shape) in enumerate(shapes):
            out[name] = uniform(jax.random.fold_in(gkey, i), (n,) + shape,
                                shape[-2], INIT_GAIN.get(name, 1.0))
        kb = jax.random.fold_in(gkey, len(shapes))
        out["ki_bias"] = jax.random.uniform(
            kb, (n, m.index_head_dim), dt, -0.1, 0.1)
        if not dense:
            out["router_bias"] = jax.random.uniform(
                jax.random.fold_in(kb, 1), (n, router_width(m)),
                jnp.float32, -0.02, 0.02)
        return out

    params = {
        "embed": jax.random.normal(jax.random.fold_in(key, 0), (V, H), dt),
        "final_norm": jnp.ones((H,), dt),
        "lm_head": uniform(jax.random.fold_in(key, 1), (H, V), H),
    }
    for i, (name, fn, n) in enumerate(layer_groups(m)):
        params[name] = group(jax.random.fold_in(key, 2 + i), n,
                             fn is dense_layer)
    return params


param_pspecs, num_params, cache_pspecs = served_whole(
    "deepseek_v32", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# serving state: the RoPE tables and the latent cache
# --------------------------------------------------------------------------- #


def serving_rope_tables(m: ModelConfig, seq_len: int, dtype) -> tuple:
    """(cos, sin) [seq_len, qk_rope_head_dim]: YaRN's blended frequencies
    when the configuration scales its RoPE."""
    return precompute_rope(seq_len, m.qk_rope_head_dim, m.rope_theta, dtype,
                           scaling=m.rope_scaling)


def softmax_scale(m: ModelConfig) -> float:
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if m.rope_scaling is not None:
        scale *= yarn_mscale(m.rope_scaling) ** 2
    return scale


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1) -> dict:
    assert not quantized and tp == 1
    return kv_cache.init_latent_cache(m, slots, max_seq_len, dtype=dtype)


# --------------------------------------------------------------------------- #
# latent attention with the learned sparse selection
# --------------------------------------------------------------------------- #


def _attend_selected(q, chosen, src: dict, layer, scale: float, rank: int,
                     pos_q):
    """Softmax attention of each query ``q`` [B, S, heads, row width] (its
    latent part, its RoPE part, zeros) over the keys ``chosen`` [B, S, T]
    for it, in the latent space: [B, S, heads, rank] float32. The window's
    live blocks of keys are read once each, where they lie, the softmax
    kept running over them (max, sum, weighted rows)."""
    B, S, nh, _ = q.shape
    T = src["ckv"].shape[2]
    Tb = _key_blocks(T, KEY_BLOCK)

    def body(j, carry):
        m, l, acc = carry
        kb = _key_block(src, "ckv", layer, j * Tb, Tb)  # [B, Tb, width]
        s = jnp.einsum("bshc,btc->bsht", q, kb,
                       preferred_element_type=jnp.float32) * scale
        on = lax.dynamic_slice_in_dim(chosen, j * Tb, Tb, axis=2)
        on = on[:, :, None, :]
        m_new = jnp.maximum(m, jnp.max(jnp.where(on, s, NEG_INF), axis=-1))
        p = jnp.where(on, jnp.exp(s - m_new[..., None]), 0.0)
        fade = jnp.exp(m - m_new)
        l = l * fade + jnp.sum(p, axis=-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "bsht,btc->bshc", p.astype(kb.dtype), kb[..., :rank],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    carry = (jnp.full((B, S, nh), NEG_INF, jnp.float32),
             jnp.zeros((B, S, nh), jnp.float32),
             jnp.zeros((B, S, nh, rank), jnp.float32))
    if T == Tb:
        _, l, acc = body(0, carry)
    else:
        _, l, acc = lax.fori_loop(0, _live_blocks(pos_q, T, Tb), body, carry)
    return acc / l[..., None]


def _attend_selected_blocks(q, chosen, src: dict, layer, scale: float,
                            rank: int, pos_q):
    """``_attend_selected``, ``QUERY_BLOCK`` queries at a time: bounds the
    [queries, heads, keys of a block] float32 scores of a chunk."""
    B, S, nh, _ = q.shape
    Sb = S if S <= QUERY_BLOCK else math.gcd(S, QUERY_BLOCK)
    if Sb == S:
        return _attend_selected(q, chosen, src, layer, scale, rank, pos_q)

    def blocks(a):  # [B, S, ...] -> [S / Sb, B, Sb, ...]
        return jnp.moveaxis(a.reshape(B, S // Sb, Sb, *a.shape[2:]), 1, 0)

    o_lat = lax.map(
        lambda xs: _attend_selected(xs[0], xs[1], src, layer, scale, rank,
                                    xs[2]),
        tuple(blocks(a) for a in (q, chosen, pos_q)))
    return jnp.moveaxis(o_lat, 0, 1).reshape(B, S, nh, rank)


def _attend_rows(q, src: dict, layer, rows, count, scale: float, rank: int):
    """A decode step's ``q`` [B, 1, heads, row width] over the rows ``rows``
    [B, n] of its slot's ``ckv`` (the first ``count`` [B] of them are chosen
    keys; the rest weigh nothing): the rows gathered, then attended densely
    as one K/V head, float32 softmax: [B, 1, heads, rank]. A row is its own
    value: of the weighted rows the latent part is kept."""
    with jax.named_scope("dsa_gather"):
        got = gather_rows(src["ckv"], layer, rows)[:, :, None]  # [B, n, 1, w]
    return kv_cache.decode_attention(q, got, got, count, scale)[..., :rank]


def attention(lp, x, cos, sin, m: ModelConfig, cache, pos, layer, live):
    """The attention half of a layer on the normed stream ``x`` [B, S, H]:
    (output [B, S, H], the cache dict with this layer's rows written (or,
    without a cache, the rows themselves as a one-layer stack), keys
    selected, keys scored, latent rows attended). ``pos`` [B] is each
    sequence's first position; ``live`` [B, S] marks the queries that are
    counted."""
    B, S, _ = x.shape
    nh, dn, dr = m.num_attention_heads, m.qk_nope_head_dim, m.qk_rope_head_dim
    dv, R = m.v_head_dim, m.kv_lora_rank
    eps = m.rms_norm_eps
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    if pos is None:
        pos = jnp.zeros((B,), jnp.int32)
    pos_q = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]

    with jax.named_scope("mla_proj"):
        c_q = rms_norm(x @ lp["wq_a"], lp["q_norm"], eps)
        q = (c_q @ lp["wq_b"]).reshape(B, S, nh, dn + dr)
        q_r = apply_rope_interleaved(q[..., dn:], cos, sin)
        kv = x @ lp["wkv_a"]
        ckv = rms_norm(kv[..., :R], lp["kv_norm"], eps)
        kr = apply_rope_interleaved(kv[..., None, R:], cos, sin)[:, :, 0]
        wkv_b = lp["wkv_b"].reshape(R, nh, dn + dv)
        q_lat = jnp.einsum("bshd,chd->bshc", q[..., :dn], wkv_b[..., :dn])
        # a cache row is [c_kv | k_r | 0 ..] out to whole lanes
        # (kv_cache.latent_widths), and a query is laid out the same
        lanes = jnp.zeros((B, S, kv_cache.latent_widths(m)["ckv"] - R - dr),
                          x.dtype)
        q_cat = jnp.concatenate(
            [q_lat, q_r, jnp.broadcast_to(lanes[:, :, None],
                                          (B, S, nh, lanes.shape[-1]))],
            axis=-1)
    with jax.named_scope("dsa_index"):
        qi = (c_q @ lp["wi_q"]).reshape(B, S, m.index_n_heads,
                                        m.index_head_dim)
        qi = jnp.concatenate(
            [apply_rope(qi[..., :dr], cos, sin), qi[..., dr:]], axis=-1)
        ki = _layer_norm(x @ lp["wi_k"], lp["ki_norm"], lp["ki_bias"])
        ki = jnp.concatenate(
            [apply_rope(ki[..., None, :dr], cos, sin)[:, :, 0],
             ki[..., dr:]], axis=-1)
        wi = (x @ lp["wi_w"]).astype(jnp.float32) \
            * (m.index_n_heads ** -0.5 * m.index_head_dim ** -0.5)

    rows = {"ckv": jnp.concatenate([ckv, kr, lanes], axis=-1), "ki": ki}
    decode = S == 1 and cache is not None and "slot" not in cache
    if cache is None:
        # a whole sequence at once: its own rows are the keys
        src = {n: r[None] for n, r in rows.items()}
        layer = 0
    else:
        src = dict(cache)
        for n, r in rows.items():
            src[n] = kv_cache.write_rows(cache, n, r, pos, layer)

    with jax.named_scope("dsa_index"):
        scores = index_scores(qi, wi, src, layer, pos_q, KEY_BLOCK)
    scale = softmax_scale(m)
    if decode:
        with jax.named_scope("dsa_select"):
            # [1, slots, T]: the slots beside the keys fill a register
            picked, count = select_rows(scores.swapaxes(0, 1), m.index_topk)
        with jax.named_scope("mla_attend"):
            o_lat = _attend_rows(q_cat, src, layer, picked[0], count[0],
                                 scale, R)
    else:
        with jax.named_scope("dsa_select"):
            chosen = select_keys(scores, m.index_topk)
        with jax.named_scope("mla_attend"):
            o_lat = _attend_selected_blocks(q_cat, chosen, src, layer, scale,
                                            R, pos_q)
    with jax.named_scope("mla_attend"):
        o = jnp.einsum("bshc,chd->bshd", o_lat.astype(x.dtype),
                       wkv_b[..., dn:])
        out = o.reshape(B, S, nh * dv) @ lp["wo"]

    kept = jnp.where(live[:, 0], count[0], 0) if decode \
        else jnp.where(live[..., None], chosen, False)
    selected = jnp.sum(kept, dtype=jnp.int32)
    scored = jnp.sum(jnp.where(live, pos_q + 1, 0), dtype=jnp.int32)
    # a decode step reads the rows it chose, a walk every live key block
    return out, src, selected, scored, selected if decode else scored


# --------------------------------------------------------------------------- #
# experts
# --------------------------------------------------------------------------- #


def held_weights(experts, weights, m: ModelConfig):
    """[N, n_routed_experts] float32: each token's weight on each expert
    held here (``ep_rank * n_routed_experts`` onward), 0 where the token
    did not choose it."""
    return expert_share.held_weights(
        experts, weights, m.ep_rank * m.n_routed_experts,
        m.n_routed_experts)


def expert_mlp(lp, x, m: ModelConfig, live) -> tuple:
    """The expert half of a layer on the normed stream ``x`` [B, S, H]:
    (this chip's part of the routed sum + the shared expert, what
    ``models/experts.py::share`` counted). Rows that are not ``live`` are
    routed nowhere."""
    B, S, H = x.shape
    x2 = x.reshape(B * S, H)
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x2.astype(jnp.float32),
                         lp["router"].astype(jnp.float32),
                         precision=lax.Precision.HIGHEST)
        experts, weights = expert_share.route(
            jax.nn.sigmoid(logits), lp["router_bias"],
            k=m.num_experts_per_tok, n_group=m.n_group,
            topk_group=m.topk_group, scale=m.routed_scaling_factor)
        w_held = held_weights(experts, weights, m) \
            * live.reshape(B * S, 1).astype(jnp.float32)
    y, counted = expert_share.share(lp, x2, w_held)
    return y.reshape(B, S, H), counted


# --------------------------------------------------------------------------- #
# the two kinds of layer
# --------------------------------------------------------------------------- #


def _layer(lp, h, cos, sin, cfg: Config, cache, pos, return_kv, layer,
           live, dense: bool):
    m = cfg.model
    if live is None:
        live = (cache or {}).get("live")
    if live is None:
        live = jnp.ones(h.shape[:2], bool)
    attn_cache = None if cache is None else {
        n: v for n, v in cache.items() if n != "live"}
    a, src, *counted = attention(
        lp, rms_norm(h, lp["attn_norm"], m.rms_norm_eps), cos, sin, m,
        attn_cache, pos, layer, live)
    h = h + a
    x = rms_norm(h, lp["mlp_norm"], m.rms_norm_eps)
    zero = jnp.zeros((), jnp.int32)
    if dense:
        h = h + _swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
        moe = (zero,) * len(expert_share.STAT_NAMES)
    else:
        y, moe = expert_mlp(lp, x, m, live)
        h = h + y
    stats = jnp.stack(moe + tuple(counted))
    if return_kv:
        # the sequence's rows, [1, S, width] each: a prefill's blocks
        out = {n: src[n][0] for n in kv_cache.LATENT_LEAVES}
    else:
        out = dict(src)
    out[STATS] = stats
    return h, out


def dense_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                return_kv: bool = False, layer=None, live=None):
    """A leading dense layer: the attention, then a SwiGLU of
    ``intermediate_size``. Same contract as ``llama.decoder_layer``; the
    returned dict also holds ``STATS``."""
    return _layer(lp, h, cos, sin, cfg, cache, pos, return_kv, layer, live,
                  dense=True)


def moe_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
              return_kv: bool = False, layer=None, live=None):
    """An expert layer: the attention, then the routed experts held here
    and the shared expert."""
    return _layer(lp, h, cos, sin, cfg, cache, pos, return_kv, layer, live,
                  dense=False)
