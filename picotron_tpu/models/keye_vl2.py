"""The Keye-VL-2.0 block (``model_type: "KeyeVL2"``, the language model of
Keye-VL-2.0-30B-A3B) as pure functions over a parameter pytree: GQA with q/k
norm a head and M-RoPE, a learned top-k selection of the keys a query
attends (a DeepSeek-style indexer over one shared key head), softmax-routed
experts with no shared one in every layer. Serving path only
(``Config.validate`` refuses the rest by name).

The equations (``x`` the normed stream; ``N`` RMSNorm with weight, eps
``rms_norm_eps``; no bias in any projection):

- stream: ``h = E[tokens]``; a layer: ``h += Attn(N1(h))``, then ``h +=
  MoE(N2(h))``; out: ``logits = Nf(h) W_head``, untied;
- attention: ``q = x W_q`` (``num_attention_heads`` of ``head_dim``), ``k = x
  W_k``, ``v = x W_v`` (``num_key_value_heads`` of ``head_dim``); ``q``, ``k``
  RMS-normed a head (one weight vector for queries, one for keys); M-RoPE on
  the whole head, halves paired, base ``rope_theta``: pair ``i``'s angle is
  its position stream's (temporal | height | width by
  ``rope_scaling.mrope_section``) times ``theta^(-2i / head_dim)``
  (``ops/rope.py::mrope_rows``; a text token's three positions are equal,
  which is plain RoPE);
- the indexer (``sa_config``): ``q^I = x W^I_q`` (``indexer_num_heads`` x
  ``indexer_head_dim``), ``k^I = LayerNorm(x W^I_k)`` (one head, weight and
  bias), both rotated over their whole width by the temporal position
  (halves, base ``rope_theta``), ``w = (x W^I_w) * heads^-0.5 * dim^-0.5`` in
  float32; ``I[t, s] = sum_h w[t, h] ReLU(q^I[t, h] . k^I[s])`` for ``s <=
  t``; the selected set of ``t`` is its ``min(topk, t + 1)`` keys of largest
  score, exact, ties to the lower index, one set for all query heads
  (``ops/select.py``);
- scores ``q . k / sqrt(head_dim)`` over the selected keys only, softmax in
  float32, ``y = concat(o) W_o``;
- experts: ``s = softmax(x W_r)`` in float32 over the router's whole width
  (``num_experts * ep_size``); the ``num_experts_per_tok`` largest (ties to
  the lower index), weights ``s[chosen] / sum`` (``norm_topk_prob``); ``y =
  sum_e w_e SwiGLU_e(x)`` over the experts held here (``ep_rank *
  num_experts`` onward); what the absent experts would add is left out.

The cache holds two leaves. ``kv`` [layers, slots, max_seq_len, 2 x kv heads,
head_dim]: a token's K heads (normed and rotated) and, behind them in the
same row, its V heads, laid as ``kv_cache.init_cache`` lays the Llama block's
K (a head a row of whole lanes, the tokens row-major). K and V lie side by
side because a decode step fetches both of every chosen token and the chip
fetches scattered rows at a rate of rows, not of bytes: one gather of 2 KB
rows took 0.26 ms a layer where two of 1 KB rows took 0.44 (PERF.md section
6, PR 46). And the indexer's keys ``ki``, ``p`` of them a row ([layers,
slots, max_seq_len / p, p x indexer_head_dim], ``p = 128 //
indexer_head_dim``: keys of 64 lie two a row, one after the other, so that
every row is whole lanes and holds no padding: ``kv_cache.py``, "The packed
row").

A prefill chunk (a ``slot`` entry) writes its rows, scores every live key a
block at a time, selects by mask and attends masked under a running softmax
over the slot's live key blocks (``_attend_chosen``; exact). A decode step
(``S == 1``) scores the slots' live ``ki``, takes the chosen keys' row
indices (``select_rows``), GATHERS those rows out of ``kv``
(``select.gather_rows``) and attends over them alone (``_attend_rows``):
``min(context, topk)`` rows of 2 KB a slot and layer where the masked walk
reads the context. Every layer function returns, beside the updated cache
leaves, what it counted (``STATS``, in the order of ``STAT_NAMES``;
docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.inference import kv_cache
from picotron_tpu.models import (STATS, live_rows, llama, normed_gqa_moe,
                                 served_whole, support)
from picotron_tpu.models import experts as expert_share
from picotron_tpu.models.llama import param_bytes  # noqa: F401 - the seam
from picotron_tpu.ops import select
from picotron_tpu.ops.attention import NEG_INF
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.rope import apply_rope, mrope_rows, precompute_rope
# under this module's name too: ``_attend_rows`` calls it by that, and the
# controls wrap it there (benchmarks/tests/control_keye.py)
from picotron_tpu.ops.select import gather_rows

# what a layer counts: the expert share's, the selection's under the names
# the DeepSeek block counts them, and the K/V rows a query's attend read
# (``min(context, topk)`` through the gather, the context through a masked
# walk)
STAT_NAMES = expert_share.STAT_NAMES + (
    "dsa_keys_selected", "dsa_keys_scored", "dsa_rows_attended")
UNSLICED = expert_share.UNSLICED
LEAVES = ("kv", "ki")  # the cache's, beside "lengths"
F32 = jnp.float32
QUERY_BLOCK = 128  # queries a masked walk attends at a time
# keys a decode step's one query a slot scores at a time: with so few rows a
# block's work is small beside its start-up, and at the 2,048 a chunk's 512
# queries take (``select.KEY_BLOCK``) the walk over 18 live blocks cost a
# step 2.3 ms where the keys' read is 0.5 (PERF.md section 6, PR 46)
DECODE_KEY_BLOCK = 8192

# Seeded weights. With every matrix U(+-sqrt(1 / fan_in)) the attention's
# output is a mean of hundreds of rows, a hundredth of the stream: which keys
# were chosen would move no logit by more than bf16's rounding does.
# ``wo`` is drawn wider (``deepseek_v32.INIT_GAIN``'s reason), the routed
# experts' ``w2`` half as wide, so that a held expert chosen on bf16 scores
# and not on float32 ones (a tie of the router broken by rounding, no fault)
# stays inside the check's limit.
INIT_GAIN = {"wo": 4.0, "w2": 0.5}
KI_BIAS = 0.1  # the indexer's LayerNorm bias, U(+-): small, and not zero

# what the block cannot do yet, and why (``support.refuse``)
WHY = {
    **support.LLAMA_ONLY,
    "training": "no backward through the top-k selection, whose indexer "
                "would need a training loss of its own, nor through the "
                "expert share",
    "tp": "the block holds no tp collectives and the indexer's one key head "
          "cannot be sharded; its share of a layer is ep_size/ep_rank",
    "dp": "the indexer's keys have no slot axis over 'dp'",
    "paged": "paged_kv.py pages K/V heads, not the indexer's keys, and its "
             "attends do not gather chosen rows; set kv_layout: 'contiguous'",
    "kv_int8": "K, V and the indexer's keys are stored in the model's dtype",
    "speculation": "a verify block's queries would each gather rows of their "
                   "own, and there is no such program",
    "flash": "the flash-decode kernels read a prefix, not chosen rows",
}


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name (``Config.validate`` calls it)."""
    m = cfg.model
    who = support.who(m)
    support.refuse(cfg, for_training, WHY)
    sa = m.sa_config or {}
    need = ("indexer_num_heads", "indexer_head_dim", "topk")
    if any(int(sa.get(n, 0)) < 1 for n in need) \
            or int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError(
            f"{who} needs model.sa_config with {', '.join(need)} each "
            f">= 1 and indexer_num_kv_heads 1 (got {m.sa_config!r})")
    support.check(m, (
        m.head_dim % 2 or int(sa["indexer_head_dim"]) % 2,
        f"head_dim {m.head_dim} and sa_config.indexer_head_dim "
        f"{sa['indexer_head_dim']} must be even (RoPE rotates halves)"))
    rs = m.rope_scaling or {}
    section = rs.get("mrope_section")
    if rs.get("rope_type", rs.get("type", "default")) != "default" \
            or not isinstance(section, list) or len(section) != 3 \
            or sum(section) != m.head_dim // 2:
        raise ValueError(
            f"{who} needs model.rope_scaling of type 'default' with an "
            f"mrope_section of three counts that sum to head_dim / 2 = "
            f"{m.head_dim // 2} (got {m.rope_scaling!r})")
    normed_gqa_moe.validate_experts(cfg)


# --------------------------------------------------------------------------- #
# shapes, groups, parameters
# --------------------------------------------------------------------------- #


router_width = normed_gqa_moe.router_width


def indexer(m: ModelConfig) -> tuple:
    """(heads, a head's width, keys kept) of ``sa_config``."""
    sa = m.sa_config
    return (int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]),
            int(sa["topk"]))


def _group_shapes(m: ModelConfig) -> dict:
    """Matmul leaves of one layer, (in, out) like every weight here; the
    routed experts lead with the experts held."""
    H = m.hidden_size
    ih, idim, _ = indexer(m)
    return {**normed_gqa_moe.attention_shapes(m),
            **normed_gqa_moe.expert_shapes(m),
            "wi_q": (H, ih * idim), "wi_k": (H, idim), "wi_w": (H, ih)}


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    layers)]: one group, the layers are alike."""
    return [("layers", decoder_layer, m.num_hidden_layers)]


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key``: linear weights U(+-gain *
    sqrt(1 / fan_in)) (``INIT_GAIN``, else 1) drawn in the model's dtype,
    norm weights ones, the embedding N(0, 1), the indexer's LayerNorm bias
    U(+-``KI_BIAS``)."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("KeyeVL2 is served on one stage (pp_size 1)")
    H, n, idim = m.hidden_size, m.num_hidden_layers, indexer(m)[1]
    shapes = _group_shapes(m)
    params = normed_gqa_moe.draw_tree(
        key, m, shapes,
        {"attn_norm": H, "mlp_norm": H, "q_norm": m.head_dim,
         "k_norm": m.head_dim, "ki_norm": idim}, INIT_GAIN)
    params["layers"]["ki_bias"] = jax.random.uniform(
        jax.random.fold_in(normed_gqa_moe.layers_key(key), len(shapes)),
        (n, idim), jnp.dtype(m.dtype), -KI_BIAS, KI_BIAS)
    return params


param_pspecs, num_params, cache_pspecs = served_whole(
    "KeyeVL2", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# into and out of the stream; serving state
# --------------------------------------------------------------------------- #


embed_lookup = llama.embed_lookup  # no multiplier
head_logits = llama.head_logits  # final norm, then the untied head


def serving_rope_tables(m: ModelConfig, seq_len: int, dtype) -> tuple:
    """(cos, sin) [seq_len, head_dim + indexer_head_dim]: a head's plain
    table in the leading columns, the indexer's (the same base over its own
    width) behind it; a layer takes each (``_angles``)."""
    pairs = [precompute_rope(seq_len, width, m.rope_theta, dtype)
             for width in (m.head_dim, indexer(m)[1])]
    return tuple(jnp.concatenate(t, axis=-1) for t in zip(*pairs))


def _angles(cos, sin, m: ModelConfig) -> tuple:
    """((cos, sin) [B, S, head_dim] of M-RoPE, (cos, sin) [B, S,
    indexer_head_dim] of the indexer) from the angle rows a layer is handed:
    [3, B, S, width] a position stream each (temporal, height, width), or
    one stream's [B, S, width] / [S, width], a text token's one position,
    which is passed three times. The indexer rotates by the temporal
    stream."""
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    if cos.ndim == 3:
        cos, sin = (jnp.broadcast_to(t, (3,) + t.shape) for t in (cos, sin))
    hd = m.head_dim
    head = mrope_rows(cos[..., :hd], sin[..., :hd],
                      m.rope_scaling["mrope_section"])
    return head, (cos[0, ..., hd:], sin[0, ..., hd:])


def ki_pack(m: ModelConfig) -> int:
    """Indexer keys that share one row of the ``ki`` leaf: as many as fill a
    register row's lanes (two of 64; one where they do not fill it whole)."""
    D = indexer(m)[1]
    return kv_cache.LANE // D if kv_cache.LANE % D == 0 else 1


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1) -> dict:
    """Zeroed cache for ``slots`` sequences: ``kv``, a token's K heads and V
    heads in one row, and the indexer's keys ``ki`` packed ``ki_pack`` a
    row."""
    assert not quantized and tp == 1
    p, D = ki_pack(m), indexer(m)[1]
    if max_seq_len % p:
        raise ValueError(
            f"KeyeVL2 needs max_seq_len ({max_seq_len}) a multiple of {p}: "
            f"the indexer's keys lie {p} a row")
    dt = jnp.dtype(dtype if dtype is not None else m.dtype)
    rows = (m.num_hidden_layers, slots, max_seq_len)
    return {"kv": jnp.zeros(rows + (2 * m.num_key_value_heads, m.head_dim),
                            dt),
            "ki": jnp.zeros(rows[:2] + (max_seq_len // p, p * D), dt),
            "lengths": jnp.zeros((slots,), jnp.int32)}


def pack_keys(ki, p: int):
    """[B, S, D] -> [B, ceil(S / p), p x D]: ``p`` keys a row, one after the
    other (a last row is filled with zeros)."""
    B, S, D = ki.shape
    ki = jnp.pad(ki, ((0, 0), (0, -S % p), (0, 0)))
    return ki.reshape(B, -1, p * D)


def write_keys(cache: dict, ki, pos, layer):
    """The ``ki`` leaf with the keys ``ki`` [B, S, D] written into ``layer``
    from positions ``pos`` [B] on, in place, in ``kv_cache.write_rows``'
    shapes of write: one slot's block (a ``slot`` entry), or a key a slot (a
    decode step). A key is a ``p``-th of a row: the rows it falls on are
    read, the new keys laid over theirs, and the rows written back (a block
    from whichever place of its first row it starts at)."""
    leaf = cache["ki"]
    B, S, D = ki.shape
    p = leaf.shape[3] // D
    if p == 1:
        return kv_cache.write_rows(cache, "ki", ki, pos, layer)
    layer = jnp.asarray(layer, jnp.int32)
    ki = ki.astype(leaf.dtype)
    if "slot" in cache or (B == 1 and S > 1):
        rows = min(-(-S // p) + 1, leaf.shape[2])
        r0 = jnp.minimum(pos[0] // p, leaf.shape[2] - rows)
        at = (layer, jnp.asarray(cache.get("slot", 0), jnp.int32), r0,
              jnp.zeros((), jnp.int32))
        old = lax.dynamic_slice(leaf, at, (1, 1, rows, p * D))
        new = lax.dynamic_update_slice(
            old.reshape(1, 1, rows * p, D), ki[None],
            (0, 0, pos[0] - r0 * p, 0))
        return lax.dynamic_update_slice(leaf, new.reshape(old.shape), at)
    if S != 1:
        raise NotImplementedError(
            "KeyeVL2 writes one slot's block or one key a slot")
    slots = jnp.arange(B)
    old = leaf[layer, slots, pos // p]  # [B, p x D]
    own = (jnp.arange(p * D) // D)[None, :] == (pos % p)[:, None]
    new = jnp.where(own, jnp.tile(ki[:, 0], (1, p)), old)
    return leaf.at[layer, slots, pos // p].set(new)


# --------------------------------------------------------------------------- #
# attention over the chosen keys
# --------------------------------------------------------------------------- #


def _attend_chosen(q, chosen, src: dict, layer, scale: float, pos_q):
    """Softmax attention of each query ``q`` [B, S, heads, D] over the keys
    ``chosen`` [B, S, T] for it: [B, S, heads, D] float32. The window's live
    blocks of ``kv`` rows are read once each, where they lie, the softmax
    kept running over them (max, sum, weighted rows):
    ``deepseek_v32._attend_selected``'s walk over K/V heads."""
    B, S, nh, D = q.shape
    T, nkv = src["kv"].shape[2], src["kv"].shape[3] // 2
    Tb = select.key_blocks(T)
    qg = q.reshape(B, S, nkv, nh // nkv, D)

    def body(j, carry):
        m, l, acc = carry
        # [B, Tb, 2 x kv, D], held to the rows' own order: left free, the
        # contractions' taste for a head's keys side by side reaches back
        # through the slice and re-lays the whole leaf, 9.7 GB, a chunk
        blk = kv_cache.row_major(
            select.key_block(src, "kv", layer, j * Tb, Tb))
        kb, vb = blk[:, :, :nkv], blk[:, :, nkv:]
        s = jnp.einsum("bskgd,btkd->bskgt", qg, kb,
                       preferred_element_type=F32) * scale
        on = lax.dynamic_slice_in_dim(chosen, j * Tb, Tb, axis=2)
        on = on[:, :, None, None, :]
        m_new = jnp.maximum(m, jnp.max(jnp.where(on, s, NEG_INF), axis=-1))
        p = jnp.where(on, jnp.exp(s - m_new[..., None]), 0.0)
        fade = jnp.exp(m - m_new)
        l = l * fade + jnp.sum(p, axis=-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "bskgt,btkd->bskgd", p.astype(vb.dtype), vb,
            preferred_element_type=F32)
        return m_new, l, acc

    lead = (B, S, nkv, nh // nkv)
    carry = (jnp.full(lead, NEG_INF, F32), jnp.zeros(lead, F32),
             jnp.zeros(lead + (D,), F32))
    if T == Tb:
        _, l, acc = body(0, carry)
    else:
        _, l, acc = lax.fori_loop(0, select.live_blocks(pos_q, T, Tb), body,
                                  carry)
    return (acc / l[..., None]).reshape(B, S, nh, D)


def _attend_chosen_blocks(q, chosen, src: dict, layer, scale: float, pos_q):
    """``_attend_chosen``, ``QUERY_BLOCK`` queries at a time: bounds the
    [queries, heads, keys of a block] float32 scores of a chunk."""
    B, S, nh, D = q.shape
    Sb = S if S <= QUERY_BLOCK else math.gcd(S, QUERY_BLOCK)
    if Sb == S:
        return _attend_chosen(q, chosen, src, layer, scale, pos_q)

    def blocks(a):  # [B, S, ...] -> [S / Sb, B, Sb, ...]
        return jnp.moveaxis(a.reshape(B, S // Sb, Sb, *a.shape[2:]), 1, 0)

    out = lax.map(
        lambda xs: _attend_chosen(xs[0], xs[1], src, layer, scale, xs[2]),
        tuple(blocks(a) for a in (q, chosen, pos_q)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, nh, D)


def _attend_rows(q, src: dict, layer, rows, count, scale: float):
    """A decode step's q [B, 1, heads, D] over the rows ``rows`` [B, n] of
    its slot's ``kv`` (the first ``count`` [B] of them are chosen keys): the
    rows gathered, then attended densely, float32 softmax."""
    with jax.named_scope("keye/gather_rows"):
        got = gather_rows(src["kv"], layer, rows)  # [B, n, 2 x kv, D]
    nkv = got.shape[2] // 2
    with jax.named_scope("keye/attend_rows"):
        return kv_cache.decode_attention(q, got[:, :, :nkv], got[:, :, nkv:],
                                         count, scale)


def attention(lp, x, cos, sin, m: ModelConfig, cache, pos, layer, live,
              return_kv: bool):
    """The attention half of a layer on the normed stream ``x`` [B, S, H]:
    (output [B, S, H], the cache leaves with this layer's rows written (or,
    without a cache, the rows a one-shot prefill would write), keys
    selected, keys scored, K/V rows attended). ``pos`` [B] is each
    sequence's first position; ``live`` [B, S] marks the queries that are
    counted."""
    B, S, _ = x.shape
    nh, hd = m.num_attention_heads, m.head_dim
    ih, idim, topk = indexer(m)
    (cos_h, sin_h), (cos_i, sin_i) = _angles(cos, sin, m)
    if pos is None:
        pos = jnp.zeros((B,), jnp.int32)
    pos_q = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]

    q, k, v = normed_gqa_moe.qkv(lp, x, m, rms_norm)
    q, k = apply_rope(q, cos_h, sin_h), apply_rope(k, cos_h, sin_h)
    with jax.named_scope("dsa_index"):
        qi = apply_rope((x @ lp["wi_q"]).reshape(B, S, ih, idim), cos_i,
                        sin_i)
        ki = select.layer_norm(x @ lp["wi_k"], lp["ki_norm"], lp["ki_bias"])
        ki = apply_rope(ki[:, :, None], cos_i, sin_i)[:, :, 0]
        wi = (x @ lp["wi_w"]).astype(F32) * (ih ** -0.5 * idim ** -0.5)

    decode = cache is not None and "slot" not in cache
    if cache is None:
        # a whole sequence at once: its own rows are the keys
        own = {"kv": jnp.concatenate([k, v], axis=2),
               "ki": pack_keys(ki, ki_pack(m))}
        src = {n: r[None] for n, r in own.items()}
        layer = 0
    else:
        src = dict(cache)
        src["kv"] = kv_cache.row_major(kv_cache.write_rows(
            cache, "kv", jnp.concatenate([k, v], axis=2), pos, layer))
        src["ki"] = kv_cache.row_major(write_keys(cache, ki, pos, layer))

    with jax.named_scope("dsa_index"):
        scores = select.index_scores(
            qi, wi, src, layer, pos_q,
            DECODE_KEY_BLOCK if decode else select.KEY_BLOCK)
        scores = scores[..., :src["kv"].shape[2]]  # less a last row's filling
    scored = jnp.sum(jnp.where(live, pos_q + 1, 0), dtype=jnp.int32)
    scale = hd ** -0.5
    if decode:
        with jax.named_scope("dsa_select"):
            # [1, slots, T]: the slots beside the keys fill a register
            rows, count = select.select_rows(scores.swapaxes(0, 1), topk)
        o = _attend_rows(q, src, layer, rows[0], count[0], scale)
        selected = attended = jnp.sum(jnp.where(live[:, 0], count[0], 0),
                                      dtype=jnp.int32)
    else:
        with jax.named_scope("dsa_select"):
            chosen = select.select_keys(scores, topk)
        with jax.named_scope("keye/attend_masked"):
            o = _attend_chosen_blocks(q, chosen, src, layer, scale, pos_q)
        selected = jnp.sum(jnp.where(live[..., None], chosen, False),
                           dtype=jnp.int32)
        attended = scored  # the walk reads every live key block
    out = o.astype(x.dtype).reshape(B, S, nh * hd) @ lp["wo"]
    if cache is None:
        leaves = own if return_kv else {}
    else:
        leaves = {n: src[n] for n in LEAVES}
    return out, leaves, selected, scored, attended


# --------------------------------------------------------------------------- #
# experts
# --------------------------------------------------------------------------- #


def router_scores(logits):
    """The router's scores [N, width] float32 of its logits: a softmax over
    the whole width."""
    return jax.nn.softmax(logits, axis=-1)


def expert_mlp(lp, x, m: ModelConfig, live) -> tuple:
    """The expert half of a layer on the normed stream ``x`` [B, S, H]:
    (this chip's part of the routed sum, what ``models/experts.py::share``
    counted). Rows that are not ``live`` are routed nowhere."""
    return normed_gqa_moe.expert_mlp(lp, x, m, live, router_scores,
                                     "keye/router")


# --------------------------------------------------------------------------- #
# the layer
# --------------------------------------------------------------------------- #


def decoder_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                  return_kv: bool = False, layer=None, live=None):
    """A layer: the attention over the chosen keys, then the routed experts
    held here, each on the normed stream. ``llama.decoder_layer``'s
    contract; the returned dict also holds ``STATS``. Three shapes of call:
    no cache (a whole sequence from position 0), a ``slot`` entry (a prefill
    chunk of that slot), neither (a decode step of every slot)."""
    m = cfg.model
    live = live_rows(cache, live, h)
    attn_cache = None if cache is None else {
        n: v for n, v in cache.items() if n not in ("live", "active")}
    a, out, selected, scored, attended = attention(
        lp, rms_norm(h, lp["attn_norm"], m.rms_norm_eps), cos, sin, m,
        attn_cache, pos, layer, live, return_kv)
    h = h + a
    y, moe = expert_mlp(lp, rms_norm(h, lp["mlp_norm"], m.rms_norm_eps), m,
                        live)
    out[STATS] = jnp.stack(moe + (selected, scored, attended))
    return h + y, out
