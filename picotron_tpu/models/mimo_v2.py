"""The MiMo-V2 block (``model_type: "mimo_v2"``, MiMo-V2.5's language model)
as pure functions over a parameter pytree: GQA attention in every layer, full
or sliding by ``hybrid_layer_pattern`` (0 | 1), the two kinds with K/V heads
of their own count, keys wider than values, a third of each head rotated
under a base of its kind, and in the sliding layers a learned sink in the
softmax; a SwiGLU or routed experts (no shared one) behind it by
``moe_layer_freq`` (0 | 1). Serving path only (``Config.validate`` refuses
the rest by name).

The equations (``x`` the normed stream; ``N`` RMSNorm with weight, eps
``rms_norm_eps``; no bias in any projection):

- stream: ``h = E[tokens]``; a layer: ``h += Attn(N1(h))``, then ``h +=
  MLP(N2(h))``; out: ``logits = Nf(h) W_head``, untied;
- attention, of a full layer (a sliding one reads the ``swa_*`` keys):
  ``q = x W_q`` (``num_attention_heads`` of ``head_dim``), ``k = x W_k``
  (``num_key_value_heads`` of ``head_dim``), ``v = attention_value_scale * x
  W_v`` (heads of ``v_head_dim``); RoPE (``rope_theta`` | ``swa_rope_theta``,
  halves paired) on the leading ``int(head_dim * partial_rotary_factor)``
  dimensions of every head of ``q`` and ``k``; scores ``q . k /
  sqrt(head_dim)``, softmax in float32; ``y = o W_o``;
  - full layer: query ``t`` sees every ``s <= t``;
  - sliding layer: query ``t`` sees keys ``s <= t`` with ``t - s <
    sliding_window``, and a sink ``b_h`` a query head joins the softmax's
    maximum and denominator and has no value: a row's weights sum to ``1 -
    exp(b_h - m) / (sum + exp(b_h - m))``;
- expert layers: ``s = sigmoid(x W_r)`` in float32 over the router's whole
  width (``n_routed_experts * ep_size``); the ``num_experts_per_tok`` largest
  of ``s + b`` (``b`` the router's correction bias, in the choice only; ties
  to the lower index); weights ``s[chosen] / (sum + 1e-20) *
  routed_scaling_factor`` (``models/experts.py::route``); ``y = sum_e w_e
  SwiGLU_e(x)`` over the experts held here (``ep_rank * n_routed_experts``
  onward); what the absent experts would add is left out.

The cache holds four leaves of four shapes, a kind's over the layers of that
kind, the heads of a row merged so that every row is whole lanes (a key head
of 192 is a lane and a half): ``k`` [full layers, slots, max_seq_len, kv
heads x head_dim], ``v`` [.., kv heads x v_head_dim]; ``kw`` [sliding
layers, slots, ring, swa kv heads x swa_head_dim], ``vw`` [.., swa kv heads x
swa_v_head_dim]; ``ring = sliding_window + prefill_chunk`` rows a slot. The
rings are ``models/afmoe.py``'s (``ring_rows``, ``ring_positions``,
``ring_write``, ``visible``, the chunk's walk): a sliding layer writes
position ``p`` at row ``p mod ring``, K cached rotated. A decode step reads
a layer's rows once (on a TPU ``flash_decode_stacked`` over the merged rows,
in its ring form with the sink for the sliding layers: of a ring's five
blocks of 128 rows it fetches those the window's rows lie in); a prefill chunk
walks its slot's live key blocks under a running softmax that starts from
the sink.

The tree: one stacked group a run of equal layers (``layer_groups``:
``dense_full_<i>``, ``moe_window_<i>``, ``moe_full_<i>``, ...); a layer finds
its row of its own kind's cache leaves from the scan's global index
(``models.leaf_row``). Every layer function returns, beside the updated
cache leaves, what it counted (``STATS``, in the order of ``STAT_NAMES``;
docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.inference import kv_cache
from picotron_tpu.models import (STATS, leaf_row, live_rows, llama, runs,
                                 served_whole, support)
from picotron_tpu.models import afmoe as rings
from picotron_tpu.models import experts as expert_share
from picotron_tpu.models.llama import param_bytes  # noqa: F401 - the seam
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.rope import apply_rope_leading, precompute_rope
from picotron_tpu.utils import on_tpu

# what a layer counts, under the names the Trinity block counts them
STAT_NAMES = rings.STAT_NAMES
UNSLICED = expert_share.UNSLICED
RING_CACHE = True  # ``init_cache`` takes the engine's ``prefill_chunk``
F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
ROUTE_EPS = 1e-20
LEAVES = ("k", "v", "kw", "vw")  # the cache's, beside "lengths"
WHY = support.RINGS  # what the block cannot do yet


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name (``Config.validate`` calls it)."""
    m = cfg.model
    who = support.who(m)
    support.refuse(cfg, for_training, WHY)
    support.positive(
        m, "sliding_window", "n_routed_experts", "num_experts_per_tok",
        "moe_intermediate_size", "ep_size", "v_head_dim",
        "swa_num_attention_heads", "swa_num_key_value_heads", "swa_head_dim",
        "swa_v_head_dim")
    n = m.num_hidden_layers
    for name in ("hybrid_layer_pattern", "moe_layer_freq"):
        got = getattr(m, name)
        if not isinstance(got, list) or len(got) != n \
                or any(v not in (0, 1) for v in got):
            raise ValueError(
                f"{who} needs model.{name}: 0 or 1 for each of the {n} "
                f"layers (got {got!r})")
    if len(set(m.hybrid_layer_pattern)) < 2:
        raise ValueError(
            f"{who} needs at least one full (0) and one sliding (1) "
            "layer in model.hybrid_layer_pattern: the cache holds "
            "leaves of each kind")
    # the leading layer is held whatever the cut
    support.held_layers(m, n - 1, lead=1, behind=" behind the leading layer")
    for heads, kv, hd, kind in (
            (m.num_attention_heads, m.num_key_value_heads, m.head_dim,
             "full"),
            (m.swa_num_attention_heads, m.swa_num_key_value_heads,
             m.swa_head_dim, "sliding")):
        rot = int(hd * m.partial_rotary_factor)
        support.check(
            m,
            (heads % kv, f"the {kind} layers' {heads} query heads must be a "
             f"multiple of their {kv} K/V heads"),
            (rot < 2 or rot % 2 or rot > hd,
             f"partial_rotary_factor {m.partial_rotary_factor} of the {kind} "
             f"layers' head_dim {hd} rotates {rot} dimensions: an even count "
             "in [2, head_dim] is needed (RoPE rotates halves)"))
    support.check(m, (
        m.num_attention_heads * m.v_head_dim
        != m.swa_num_attention_heads * m.swa_v_head_dim
        or int(m.head_dim * m.partial_rotary_factor)
        != int(m.swa_head_dim * m.partial_rotary_factor),
        "both kinds of layer must rotate as many dimensions (one table holds "
        "both bases) and hand W_o as many columns"))
    support.ep_share(m, "n_routed_experts")
    support.check(m, (
        m.layernorm_epsilon and m.layernorm_epsilon != m.rms_norm_eps,
        f"layernorm_epsilon {m.layernorm_epsilon} is not rms_norm_eps "
        f"{m.rms_norm_eps} (the norms read the latter)"))
    if (m.rope_scaling or {}).get("rope_type", "default") != "default":
        raise ValueError(
            f"{who} implements model.rope_scaling of type 'default' "
            f"(none) only (got {m.rope_scaling!r})")
    support.pinned(m, scoring_func="sigmoid", topk_method="noaux_tc",
                   norm_topk_prob=True, n_group=1, topk_group=1,
                   n_shared_experts=0, add_swa_attention_sink_bias=True,
                   add_full_attention_sink_bias=False,
                   tie_word_embeddings=False)

# Seeded weights. The block has no norm behind a branch, so what a branch
# adds to the stream is as loud as its matrices' draw makes it. With every
# matrix U(+-sqrt(1 / fan_in)) the attention's output is a mean of a
# hundred rows and more, a fiftieth of the stream: neither a sink left out
# nor a ring's wrong row would move a logit by more than bf16's rounding
# does. ``wo`` is drawn eight times as wide (``deepseek_v32.INIT_GAIN``'s
# reason); the routed experts' ``w2`` half as wide, so that a held expert
# chosen on bf16 scores and not on float32 ones (a tie of the router broken
# by rounding, no fault) stays inside the check's limit.
INIT_GAIN = {"wo": 8.0, "w2": 0.5}
ROUTER_BIAS = 0.02  # the correction bias's draw, U(+-): small, and not zero
# the sinks' draw: N(log(sliding_window), 1) in float32. The softmax of the
# draw above is flat (its logits spread by a third), so the keys of a full
# window sum to about ``sliding_window``: a sink of N(0, 1) would take a
# hundredth of a row's mass and leaving it out would pass any comparison;
# drawn about the window's logarithm it takes a half, more or less.


# --------------------------------------------------------------------------- #
# shapes, groups, parameters
# --------------------------------------------------------------------------- #


def router_width(m: ModelConfig) -> int:
    return m.n_routed_experts * m.ep_size


def heads(m: ModelConfig, window: bool) -> tuple:
    """(query heads, K/V heads, key head's width, value head's width) of a
    sliding (``window``) or a full layer."""
    if window:
        return (m.swa_num_attention_heads, m.swa_num_key_value_heads,
                m.swa_head_dim, m.swa_v_head_dim)
    return (m.num_attention_heads, m.num_key_value_heads, m.head_dim,
            m.v_head_dim)


def rotated_dims(m: ModelConfig) -> int:
    """Leading dimensions of a head RoPE rotates (both kinds alike)."""
    return int(m.head_dim * m.partial_rotary_factor)


def layer_kinds(m: ModelConfig) -> list:
    """One name a layer: ``dense`` | ``moe`` (what follows the attention),
    then ``window`` | ``full`` (what the attention sees)."""
    return [("moe" if f else "dense") + "_" + ("window" if p else "full")
            for f, p in zip(m.moe_layer_freq, m.hybrid_layer_pattern)]


def kind_counts(m: ModelConfig) -> dict:
    n = sum(m.hybrid_layer_pattern)
    return {"window": n, "full": len(m.hybrid_layer_pattern) - n}


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    layers)]: one group a run of equal ``layer_kinds``, scanned in turn
    (``afmoe.layer_groups``)."""
    kinds = layer_kinds(m)
    sees = [k.split("_")[1] for k in kinds]  # window | full, a layer
    return [(f"{kind}_{i}",
             partial(_layer, dense=kind.startswith("dense"),
                     window=sees[first] == "window", first=first,
                     kind_first=sees[:first].count(sees[first])), n)
            for i, (kind, first, _, n) in enumerate(runs(kinds))]


def _group_shapes(m: ModelConfig, dense: bool, window: bool) -> dict:
    """Matmul leaves of one layer of a group, (in, out) like every weight
    here; the routed experts lead with the experts held."""
    H = m.hidden_size
    nh, nkv, hd, vd = heads(m, window)
    shapes = {"wq": (H, nh * hd), "wk": (H, nkv * hd), "wv": (H, nkv * vd),
              "wo": (nh * vd, H)}
    if dense:
        I = m.intermediate_size
        shapes.update(w_gate=(H, I), w_up=(H, I), w_down=(I, H))
        return shapes
    E, I = m.n_routed_experts, m.moe_intermediate_size
    shapes.update(router=(H, router_width(m)),
                  w1=(E, H, I), w3=(E, H, I), w2=(E, I, H))
    return shapes


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key``: linear weights U(+-gain *
    sqrt(1 / fan_in)) (``INIT_GAIN``, else 1) drawn in the model's dtype,
    norm weights ones, the embedding N(0, 1), the router's bias U(+-
    ``ROUTER_BIAS``) and the sinks N(log(sliding_window), 1), both in
    float32."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("mimo_v2 is served on one stage (pp_size 1)")
    dt = jnp.dtype(m.dtype)
    H, V = m.hidden_size, m.vocab_size

    def uniform(k, shape, fan_in, gain=1.0):
        bound = gain * math.sqrt(1.0 / fan_in)
        return jax.random.uniform(k, shape, dt, -bound, bound)

    def group(gkey, n: int, dense: bool, window: bool) -> dict:
        out = {"attn_norm": jnp.ones((n, H), dt),
               "mlp_norm": jnp.ones((n, H), dt)}
        shapes = sorted(_group_shapes(m, dense, window).items())
        for i, (name, shape) in enumerate(shapes):
            out[name] = uniform(jax.random.fold_in(gkey, i), (n,) + shape,
                                shape[-2], INIT_GAIN.get(name, 1.0))
        if not dense:
            out["router_bias"] = jax.random.uniform(
                jax.random.fold_in(gkey, len(shapes)), (n, router_width(m)),
                F32, -ROUTER_BIAS, ROUTER_BIAS)
        if window:
            out["sink"] = math.log(m.sliding_window) + jax.random.normal(
                jax.random.fold_in(gkey, len(shapes) + 1),
                (n, heads(m, True)[0]), F32)
        return out

    params = {
        "embed": jax.random.normal(jax.random.fold_in(key, 0), (V, H),
                                   F32).astype(dt),
        "final_norm": jnp.ones((H,), dt),
        "lm_head": uniform(jax.random.fold_in(key, 1), (H, V), H),
    }
    for i, (name, _, n) in enumerate(layer_groups(m)):
        params[name] = group(jax.random.fold_in(key, 2 + i), n,
                             name.startswith("dense"), "_window_" in name)
    return params


param_pspecs, num_params, cache_pspecs = served_whole(
    "mimo_v2", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# into and out of the stream; serving state
# --------------------------------------------------------------------------- #


embed_lookup = llama.embed_lookup  # no multiplier
head_logits = llama.head_logits  # final norm, then the untied head


def serving_rope_tables(m: ModelConfig, seq_len: int, dtype) -> tuple:
    """(cos, sin) [seq_len, 2 x rotated dims]: the full layers' pair
    (``rope_theta``) in the leading half of the columns, the sliding
    layers' (``swa_rope_theta``) in the other; a layer takes its half
    (``_own_tables``)."""
    rot = rotated_dims(m)
    pairs = [precompute_rope(seq_len, rot, base, dtype)
             for base in (m.rope_theta, m.swa_rope_theta)]
    return tuple(jnp.concatenate(t, axis=-1) for t in zip(*pairs))


def _own_tables(cos, sin, window: bool) -> tuple:
    rot = cos.shape[-1] // 2
    half = slice(rot, None) if window else slice(0, rot)
    return cos[..., half], sin[..., half]


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1,
               prefill_chunk: int = 0) -> dict:
    """Zeroed cache for ``slots`` sequences, four leaves of four shapes (the
    module docstring): a kind's K and V over the layers of that kind, a
    row's heads merged."""
    assert not quantized and tp == 1
    dt = jnp.dtype(dtype if dtype is not None else m.dtype)
    n = kind_counts(m)
    full = (n["full"], slots, max_seq_len)
    ring = (n["window"], slots,
            rings.ring_rows(m, max_seq_len, prefill_chunk or max_seq_len))
    _, nkv, hd, vd = heads(m, False)
    _, nkw, hw, vw = heads(m, True)
    return {"k": jnp.zeros(full + (nkv * hd,), dt),
            "v": jnp.zeros(full + (nkv * vd,), dt),
            "kw": jnp.zeros(ring + (nkw * hw,), dt),
            "vw": jnp.zeros(ring + (nkw * vw,), dt),
            "lengths": jnp.zeros((slots,), jnp.int32)}


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #


def decode_attend(q, k_leaf, v_leaf, pos, row, window: int, scale: float,
                  sink, nkv: int, impl: str = "auto"):
    """A decode step's q [B, 1, heads, D] (at positions ``pos`` [B], its own
    K/V written) over layer ``row`` of a kind's stacked leaves [layers,
    slots, T, kv heads x D]: a ring with a ``window`` (rows older than it
    masked, a ``sink`` in the softmax), else a prefix. Under ``impl`` "auto"
    on a TPU, bfloat16 rows of whole lanes go through the stacked
    flash-decode kernel, where they lie, every head of a row side by side;
    else a masked contraction of the layer's block."""
    T = k_leaf.shape[2]
    if impl == "auto" and on_tpu() \
            and q.dtype == k_leaf.dtype == jnp.bfloat16 \
            and k_leaf.shape[3] % kv_cache.LANE == 0 \
            and v_leaf.shape[3] % kv_cache.LANE == 0:
        from picotron_tpu.ops.pallas.decode_attention import (
            flash_decode_stacked,
        )

        return flash_decode_stacked(
            q, k_leaf[:, :, :, None], v_leaf[:, :, :, None], pos + 1, scale,
            row, window=window or None, sink=sink)
    B = q.shape[0]
    pos_k = rings.ring_positions(pos, T) if window else jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32), (B, T))
    kb, vb = (lax.dynamic_index_in_dim(leaf, row, 0, False).reshape(
        B, T, nkv, -1) for leaf in (k_leaf, v_leaf))
    return rings.masked_attention(
        q, kb, vb, rings.visible(pos[:, None], pos_k, window), scale, sink)


def attention(lp, x, cos, sin, cfg: Config, cache, pos, row, live,
              window: bool, return_kv: bool):
    """The attention on the normed stream ``x`` [B, S, H]: (output
    [B, S, H], the cache leaves it wrote (or the rows a one-shot prefill
    would), keys the live queries attended, keys up to them)."""
    m = cfg.model
    B, S, _ = x.shape
    nh, nkv, hd, vd = heads(m, window)
    W = m.sliding_window if window else 0
    scale = hd ** -0.5
    names = ("kw", "vw") if window else ("k", "v")
    sink = lp.get("sink")  # the sliding groups' leaf
    q = (x @ lp["wq"]).reshape(B, S, nh, hd)
    k = (x @ lp["wk"]).reshape(B, S, nkv, hd)
    v = ((x @ lp["wv"]) * jnp.asarray(m.attention_value_scale, x.dtype)
         ).reshape(B, S, nkv, vd)
    cos, sin = _own_tables(cos, sin, window)
    q, k = apply_rope_leading(q, cos, sin), apply_rope_leading(k, cos, sin)
    steps = jnp.arange(S, dtype=jnp.int32)[None, :]
    merged = [r.reshape(B, S, -1) for r in (k, v)]  # as the leaves lie
    with jax.named_scope("mimo/window_attend" if window
                         else "mimo/full_attend"):
        if cache is None:
            # a whole sequence from position 0, nothing cached
            pos_q = jnp.broadcast_to(steps, (B, S))
            a = rings.masked_attention(
                q, k, v, rings.visible(pos_q, pos_q, W), scale, sink)
            # a ring takes the rows a one-shot prompt can fill: those of a
            # chunk (longer prompts go in chunks), which every ring holds
            keep = cfg.inference.prefill_chunk if window else S
            out = {n: r[:, :keep] for n, r in zip(names, merged)} \
                if return_kv else {}
        else:
            pos_q = pos[:, None] + steps
            out = {n: cache[n] for n in LEAVES}
            slot = cache.get("slot")
            for n, new in zip(names, merged):
                if window:
                    wrote = rings.ring_write(cache[n], new, pos, row, slot)
                else:
                    meta = {} if slot is None else {"slot": slot}
                    wrote = kv_cache.write_rows({n: cache[n], **meta}, n,
                                                new, pos, row)
                out[n] = kv_cache.row_major(wrote)
            if slot is not None:
                # one slot's chunk: its strip walked in blocks of keys
                a = rings.chunk_attention(
                    q, out[names[0]], out[names[1]], row,
                    jnp.asarray(slot, jnp.int32), pos_q, W, scale,
                    sink=sink, kv_heads=nkv)
            else:
                a = decode_attend(q, out[names[0]], out[names[1]], pos, row,
                                  W, scale, sink, nkv,
                                  impl=cfg.inference.attend_impl)
    a = a.reshape(B, S, -1) @ lp["wo"]
    context = jnp.where(live, pos_q + 1, 0)
    attended = jnp.minimum(context, W) if window else context
    return a, out, jnp.sum(attended, dtype=jnp.int32), \
        jnp.sum(context, dtype=jnp.int32)


# --------------------------------------------------------------------------- #
# experts
# --------------------------------------------------------------------------- #


def expert_mlp(lp, x, m: ModelConfig, live) -> tuple:
    """The expert half of a layer on the normed stream ``x`` [B, S, H]:
    (this chip's part of the routed sum, what ``models/experts.py::share``
    counted). Rows that are not ``live`` are routed nowhere."""
    B, S, H = x.shape
    x2 = x.reshape(B * S, H)
    with jax.named_scope("mimo/router"):
        logits = jnp.dot(x2.astype(F32), lp["router"].astype(F32),
                         precision=HIGHEST)
        experts, weights = expert_share.route(
            jax.nn.sigmoid(logits), lp["router_bias"],
            k=m.num_experts_per_tok, scale=m.routed_scaling_factor,
            eps=ROUTE_EPS)
        w_held = expert_share.held_weights(
            experts, weights, m.ep_rank * m.n_routed_experts,
            m.n_routed_experts) * live.reshape(B * S, 1).astype(F32)
    y, counted = expert_share.share(lp, x2, w_held)
    return y.reshape(B, S, H), counted


# --------------------------------------------------------------------------- #
# the layer
# --------------------------------------------------------------------------- #


def _layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
           return_kv: bool = False, layer=None, live=None, *, dense: bool,
           window: bool, first: int = 0, kind_first: int = 0):
    """A layer: the attention (``window``: sliding, else full), then a
    SwiGLU (``dense``) or the experts, each on the normed stream.
    ``llama.decoder_layer``'s contract; the returned dict also holds
    ``STATS``. Three shapes of call, as ``afmoe._layer``: no cache, a
    ``slot`` entry (a prefill chunk of that slot), neither (a decode step of
    every slot)."""
    m = cfg.model
    eps = m.rms_norm_eps
    live = live_rows(cache, live, h)
    row = None if cache is None else leaf_row(layer, first, kind_first)
    a, out, attended, context = attention(
        lp, rms_norm(h, lp["attn_norm"], eps), cos, sin, cfg, cache, pos,
        row, live, window, return_kv)
    h = h + a
    x = rms_norm(h, lp["mlp_norm"], eps)
    zero = jnp.zeros((), jnp.int32)
    if dense:
        y = expert_share.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
        moe = (zero,) * len(expert_share.STAT_NAMES)
    else:
        y, moe = expert_mlp(lp, x, m, live)
    h = h + y
    decode = cache is not None and "slot" not in cache
    swa = ((attended, context, zero + int(decode)) if window
           else (zero, zero, zero))
    out[STATS] = jnp.stack(moe + swa)
    return h, out
