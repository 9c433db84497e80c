"""The Trinity block (``model_type: "afmoe"``) as pure functions over a
parameter pytree: gated GQA attention in every layer, over the last
``sliding_window`` keys with RoPE in the ``sliding_attention`` layers and
over everything with no position embedding in the ``full_attention`` ones
(``layer_types``); a SwiGLU behind the first ``num_dense_layers`` layers,
routed experts and a shared one behind the others. Serving path only
(``Config.validate`` refuses the rest by name).

The equations (``x`` the normed stream; ``N`` RMSNorm with weight, eps
``rms_norm_eps``; no bias anywhere):

- stream: ``h = sqrt(hidden_size) * E[tokens]`` (``mup_enabled``); a layer:
  ``h += N2(Attn(N1(h)))``, then ``h += N4(MLP(N3(h)))`` (the post norms sit
  on the branch); out: ``logits = Nf(h) W_head``, untied;
- attention: ``q = x W_q``, ``k = x W_k``, ``v = x W_v`` (GQA, heads of
  ``head_dim``, which is not ``hidden_size / heads``); RMSNorm over each
  head's ``head_dim`` of ``q`` and of ``k`` (one weight vector a layer
  each); scores ``q . k / sqrt(head_dim)``, softmax in float32; ``o *=
  sigmoid(x W_g)``, elementwise over all heads; ``y = o W_o``;
  - sliding layer: RoPE (``rope_theta``, halves paired) on ``q`` and ``k``
    at the token's position; query ``t`` sees keys ``s <= t`` with ``t - s
    < sliding_window``;
  - full layer: no position embedding; query ``t`` sees every ``s <= t``;
- expert layers: ``s = sigmoid(x W_r)`` in float32 over the router's whole
  width (``num_experts * ep_size``); the ``num_experts_per_tok`` largest of
  ``s + b`` (``b`` the router's bias buffer, in the choice only; ties to the
  lower index); weights ``s[chosen] / (sum + 1e-20) * route_scale``
  (``models/experts.py::route``); ``y = SwiGLU_shared(x) + sum_e w_e
  SwiGLU_e(x)``. This chip holds ``num_experts`` of the experts (``ep_rank *
  num_experts`` onward) and adds their part and the shared expert's
  (``models/experts.py``); what the absent experts would add is left out.

The cache holds two kinds of K/V, each over the layers of its own kind:
``k``/``v`` ``[full layers, slots, max_seq_len, kv heads, head_dim]`` and
``kw``/``vw`` ``[sliding layers, slots, ring, kv heads, head_dim]``, ``ring =
sliding_window + prefill_chunk`` rows a slot (``ring_rows``). A sliding
layer writes position ``p`` at row ``p mod ring``; K is cached rotated, so
the order of a ring's rows does not matter to the softmax. After a write
that ends at position ``e``, row ``r`` holds position ``e - ((e - r) mod
ring)`` (``ring_positions``): the ring is a chunk longer than the window so
that the rows a prefill chunk overwrites (its pad rows too) are older than
anything one of its queries may see. A decode step reads the ring's rows
once and masks those older than the window (``ring_attend``: on a TPU the
stacked flash-decode kernel in its ring form); a prefill chunk walks the live
key blocks of its slot's strip (ring or prefix) with a running softmax.

The tree: one stacked group a run of equal layers (``layer_groups``:
``dense_window_<i>``, ``moe_window_<i>``, ``moe_full_<i>``, ...); a layer
finds its row of its own kind's cache leaves from the scan's global index
(``models.leaf_row``).

Every layer function returns, beside the updated cache leaves, what it
counted (``STATS``, in the order of ``STAT_NAMES``; docs/OBSERVABILITY.md).

The rings and the attends over them (``ring_rows`` to ``ring_write``,
``visible``, ``masked_attention``, ``chunk_attention``) also serve
``models/mimo_v2.py``, which imports them: there a row's heads are merged
(``kv_heads``), V's heads are narrower than K's, and the sliding softmax has
a ``sink``; this block calls them with none of the three.
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
from picotron_tpu.models import experts as expert_share
from picotron_tpu.models.llama import param_bytes  # noqa: F401 - the seam
from picotron_tpu.ops.attention import NEG_INF
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.rope import apply_rope, precompute_rope
from picotron_tpu.utils import on_tpu

# what a layer counts, in the order of the vector (under ``STATS``): the
# expert share's (``experts.STAT_NAMES``); keys the sliding layers'
# live queries attended, keys they would have with no window, sliding
# layers decode steps ran
STAT_NAMES = expert_share.STAT_NAMES + (
    "swa_rows_attended", "swa_rows_context", "swa_layer_steps")

UNSLICED = expert_share.UNSLICED
# the sliding layers' leaves are rings a prefill chunk's writes must fit:
# the engine hands ``init_cache`` its ``prefill_chunk``
RING_CACHE = True
F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
WINDOW, FULL = "sliding_attention", "full_attention"
ROTATED = (WINDOW,)  # the kinds of layer whose q and k take RoPE
# keys a prefill chunk attends at a time: bounds the [heads, chunk, keys]
# float32 scores (48 x 512 x 2048: 201 MB)
KEY_BLOCK = 2048
ROUTE_EPS = 1e-20
LEAVES = ("k", "v", "kw", "vw")  # the cache's, beside "lengths"
WHY = support.RINGS  # what the block cannot do yet


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name (``Config.validate`` calls it)."""
    m = cfg.model
    support.refuse(cfg, for_training, WHY)
    support.positive(m, "sliding_window", "num_experts", "num_shared_experts",
                     "num_experts_per_tok", "moe_intermediate_size",
                     "ep_size")
    kinds = (WINDOW, FULL)
    support.layer_kinds(m, "layer_types", kinds)
    support.check(m, (
        not 0 <= m.num_dense_layers < m.num_hidden_layers,
        f"num_dense_layers {m.num_dense_layers} outside [0, "
        f"num_hidden_layers {m.num_hidden_layers})"))
    every = m.global_attn_every_n_layers
    if every:
        support.held_layers(
            m, m.num_hidden_layers - m.num_dense_layers,
            lead=m.num_dense_layers, what="expert layers",
            behind=f" behind num_dense_layers {m.num_dense_layers}")
        first = m.first_layer if m.total_layers else m.num_dense_layers
        for i, t in enumerate(m.layer_types):
            pub = i if i < m.num_dense_layers \
                else first + i - m.num_dense_layers
            support.check(m, (
                (t == FULL) != ((pub + 1) % every == 0),
                f"layer_types[{i}] {t!r} is published layer {pub}, which "
                f"global_attn_every_n_layers {every} makes "
                f"{kinds[(pub + 1) % every == 0]!r}"))
    support.check(m, (
        m.head_dim % 2,
        f"head_dim {m.head_dim} must be even (RoPE rotates halves)"))
    support.ep_share(m, "num_experts")
    support.pinned(m, score_func="sigmoid", route_norm=True, n_group=1,
                   topk_group=1, tie_word_embeddings=False,
                   rope_scaling=None)


# --------------------------------------------------------------------------- #
# shapes, groups, parameters
# --------------------------------------------------------------------------- #


def router_width(m: ModelConfig) -> int:
    return m.num_experts * m.ep_size


def layer_kinds(m: ModelConfig) -> list:
    """One name a layer: ``dense`` | ``moe`` (what follows the attention),
    then ``window`` | ``full`` (what the attention sees)."""
    return [("dense" if i < m.num_dense_layers else "moe") + "_"
            + ("window" if t == WINDOW else "full")
            for i, t in enumerate(m.layer_types)]


def kind_counts(m: ModelConfig) -> dict:
    return {"window": sum(t == WINDOW for t in m.layer_types),
            "full": sum(t == FULL for t in m.layer_types)}


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    layers)]: one group a run of equal ``layer_kinds``, scanned in turn. Each
    function knows where its run begins, among all layers and among those
    whose attention is of its kind (its rows of the cache)."""
    kinds = layer_kinds(m)
    sees = [k.split("_")[1] for k in kinds]  # window | full, a layer
    return [(f"{kind}_{i}",
             partial(_layer, dense=kind.startswith("dense"),
                     window=sees[first] == "window", first=first,
                     kind_first=sees[:first].count(sees[first])), n)
            for i, (kind, first, _, n) in enumerate(runs(kinds))]


def _group_shapes(m: ModelConfig, dense: bool) -> dict:
    """Matmul leaves of one layer of a group, (in, out) like every weight
    here; the routed experts lead with the experts held."""
    H, hd = m.hidden_size, m.head_dim
    nq, nkv = m.num_attention_heads * hd, m.num_key_value_heads * hd
    shapes = {"wq": (H, nq), "wk": (H, nkv), "wv": (H, nkv), "wg": (H, nq),
              "wo": (nq, H)}
    if dense:
        I = m.intermediate_size
        shapes.update(w_gate=(H, I), w_up=(H, I), w_down=(I, H))
        return shapes
    E, I = m.num_experts, m.moe_intermediate_size
    Is = m.num_shared_experts * I
    shapes.update(router=(H, router_width(m)),
                  w1=(E, H, I), w3=(E, H, I), w2=(E, I, H),
                  ws_gate=(H, Is), ws_up=(H, Is), ws_down=(Is, H))
    return shapes


# Seeded weights. The post norms make every branch a unit-rms vector
# whatever its matrices' draw, so the attention needs no gain to be heard
# beside the MLP (as ``deepseek_v32.INIT_GAIN`` gives ``wo``); what a gain
# can still set is one part of a branch against another. The routed experts'
# ``w2`` is drawn a sixteenth as wide, so that the shared expert carries the
# expert branch: a held expert chosen on bf16 scores and not on float32 ones
# (a tie of the router broken by rounding, which is no fault, and which the
# sound program's own bf16 stream can break as well) read 4.7 % of max
# |logit| on one seed of three at a quarter, over the check's limit of 3; the
# shares are held to the reference in float32 by tier-1 tests whatever the
# gain (PERF.md section 6, PR 39, has the readings).
INIT_GAIN = {"w2": 0.0625}
# the embedding's draw: N(0, 1) / sqrt(hidden_size), so that ``mup_enabled``'s
# sqrt(hidden_size) gives the stream a unit-rms entry: an N(0, 1) row times
# 55 would bury the eighteen unit-rms branches behind the token's own row
ROUTER_BIAS = 0.02  # the bias buffer's draw, U(+-): small, and not zero


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key``: linear weights U(+-gain *
    sqrt(1 / fan_in)) (``INIT_GAIN``, else 1) drawn in the model's dtype,
    norm weights ones, the router's bias buffer U(+-``ROUTER_BIAS``) in
    float32, the embedding N(0, 1) / sqrt(hidden_size)."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("afmoe is served on one stage (pp_size 1)")
    dt = jnp.dtype(m.dtype)
    H, V = m.hidden_size, m.vocab_size

    def uniform(k, shape, fan_in, gain=1.0):
        bound = gain * math.sqrt(1.0 / fan_in)
        return jax.random.uniform(k, shape, dt, -bound, bound)

    def group(gkey, n: int, dense: bool) -> dict:
        ones = lambda w: jnp.ones((n, w), dt)
        out = {"attn_norm": ones(H), "post_attn_norm": ones(H),
               "mlp_norm": ones(H), "post_mlp_norm": ones(H),
               "q_norm": ones(m.head_dim), "k_norm": ones(m.head_dim)}
        shapes = sorted(_group_shapes(m, dense).items())
        for i, (name, shape) in enumerate(shapes):
            out[name] = uniform(jax.random.fold_in(gkey, i), (n,) + shape,
                                shape[-2], INIT_GAIN.get(name, 1.0))
        if not dense:
            out["router_bias"] = jax.random.uniform(
                jax.random.fold_in(gkey, len(shapes)), (n, router_width(m)),
                F32, -ROUTER_BIAS, ROUTER_BIAS)
        return out

    params = {
        "embed": (jax.random.normal(jax.random.fold_in(key, 0), (V, H), F32)
                  / math.sqrt(H)).astype(dt),
        "final_norm": jnp.ones((H,), dt),
        "lm_head": uniform(jax.random.fold_in(key, 1), (H, V), H),
    }
    for i, (name, _, n) in enumerate(layer_groups(m)):
        params[name] = group(jax.random.fold_in(key, 2 + i), n,
                             name.startswith("dense"))
    return params


param_pspecs, num_params, cache_pspecs = served_whole(
    "afmoe", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# into and out of the stream; serving state
# --------------------------------------------------------------------------- #


def embed_lookup(w, tokens, cfg: Config):
    """``E[tokens]``, times ``sqrt(hidden_size)`` under ``mup_enabled``, in
    the embedding's dtype."""
    e = llama.embed_lookup(w, tokens)
    if cfg.model.mup_enabled:
        e = e * jnp.asarray(math.sqrt(cfg.model.hidden_size), w.dtype)
    return e


head_logits = llama.head_logits  # final norm, then the untied head


def serving_rope_tables(m: ModelConfig, seq_len: int, dtype) -> tuple:
    """(cos, sin) [seq_len, head_dim], unscaled: the sliding layers read
    them, the full layers do not."""
    return precompute_rope(seq_len, m.head_dim, m.rope_theta, dtype)


def ring_rows(m: ModelConfig, max_seq_len: int, prefill_chunk: int) -> int:
    """Rows a slot of a sliding layer keeps: the window and one prefill
    chunk more, so that a chunk's writes land only on rows older than any
    of its queries sees; never more than the cache window itself."""
    return min(m.sliding_window + prefill_chunk, max_seq_len)


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1,
               prefill_chunk: int = 0) -> dict:
    """Zeroed cache for ``slots`` sequences, two kinds of K/V, each over the
    layers of its own kind: ``k``/``v`` [full layers, slots, max_seq_len, kv
    heads, head_dim]; ``kw``/``vw`` [sliding layers, slots, ring, kv heads,
    head_dim] (``ring_rows``)."""
    assert not quantized and tp == 1
    dt = jnp.dtype(dtype if dtype is not None else m.dtype)
    n = kind_counts(m)
    row = (m.num_key_value_heads, m.head_dim)
    full = (n["full"], slots, max_seq_len) + row
    ring = (n["window"], slots,
            ring_rows(m, max_seq_len, prefill_chunk or max_seq_len)) + row
    return {"k": jnp.zeros(full, dt), "v": jnp.zeros(full, dt),
            "kw": jnp.zeros(ring, dt), "vw": jnp.zeros(ring, dt),
            "lengths": jnp.zeros((slots,), jnp.int32)}


# --------------------------------------------------------------------------- #
# attention
# --------------------------------------------------------------------------- #


def ring_positions(end, ring: int, rows=None):
    """[..., rows] int32: the position each of ``rows`` (all, without) of a
    ring holds once position ``end`` [...] is written (negative: not
    written yet)."""
    if rows is None:
        rows = jnp.arange(ring, dtype=jnp.int32)
    end = jnp.asarray(end, jnp.int32)[..., None]
    return end - (end - rows) % ring


def visible(pos_q, pos_k, window: int):
    """[B, S, T] bool: which keys (at positions ``pos_k`` [B, T]; negative:
    no key) a query at ``pos_q`` [B, S] sees: those not after it and, with a
    ``window``, fewer than ``window`` positions behind it."""
    pq, pk = pos_q[:, :, None], pos_k[:, None, :]
    ok = (pk >= 0) & (pk <= pq)
    return ok & (pq - pk < window) if window else ok


def _scores(q, k, scale: float):
    """[B, kv heads, group, S, T] float32 from q [B, S, heads, D] and k
    [B, T, kv heads, D]: GQA by a grouped contraction, nothing repeated."""
    B, S, nh, D = q.shape
    qg = q.reshape(B, S, k.shape[2], nh // k.shape[2], D)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k,
                      preferred_element_type=F32) * scale


def sink_rows(sink, nkv: int):
    """A sink's [heads] float32 as [1, kv heads, group, 1, 1]: one scalar a
    query head, beside the scores ``_scores`` lays out."""
    return sink.astype(F32).reshape(1, nkv, -1, 1, 1)


def masked_attention(q, k, v, seen, scale: float, sink=None):
    """Softmax attention of q [B, S, heads, D] over k [B, T, kv heads, D]
    and v [B, T, kv heads, Dv] under ``seen`` [B, S, T], float32 softmax
    (``kv_cache.decode_attention`` with the mask handed in). A ``sink``
    [heads] joins each head's maximum and denominator and has no value: the
    rows then sum to less than 1 by its share."""
    s = jnp.where(seen[:, None, None], _scores(q, k, scale), NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sink = sink_rows(sink, k.shape[2])
        m = jnp.maximum(m, sink)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        l = l + jnp.exp(sink - m)
    p = p / l
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(F32))
    return out.reshape(q.shape[:3] + v.shape[3:]).astype(q.dtype)


def key_block(T: int) -> int:
    """The largest divisor of ``T`` no larger than ``KEY_BLOCK``."""
    return max(b for b in range(1, min(T, KEY_BLOCK) + 1) if T % b == 0)


def chunk_attention(q, k_leaf, v_leaf, row, slot, pos_q, window: int,
                    scale: float, sink=None, kv_heads: int = 0):
    """A prefill chunk's q [1, S, heads, D] (at positions ``pos_q`` [1, S],
    its own K/V already written) over ``slot``'s strip of layer ``row`` of
    the stacked leaves [layers, slots, T, kv heads, D] (or, with
    ``kv_heads``, the heads merged: [layers, slots, T, kv heads x D]; V's
    heads may be narrower than K's), where it lies: the strip's live key
    blocks one after the other under a running softmax, so that the float32
    scores never pass [heads, S, ``KEY_BLOCK``]. With a ``window`` the strip
    is a ring (``ring_positions``), else a prefix. A ``sink`` [heads] is
    where the running softmax starts: its maximum, a denominator of 1,
    nothing accumulated."""
    T = k_leaf.shape[2]
    Tb = key_block(T)
    end = pos_q[0, -1]
    blocks = (jnp.minimum(end + 1, T) + Tb - 1) // Tb
    _, S, nh, _ = q.shape
    nkv = kv_heads or k_leaf.shape[3]
    Dv = math.prod(v_leaf.shape[3:]) // nkv
    zero = jnp.zeros((), jnp.int32)

    def block(leaf, j):
        at = (row, slot, j * Tb) + (zero,) * (leaf.ndim - 3)
        got = lax.dynamic_slice(leaf, at, (1, 1, Tb) + leaf.shape[3:])[0]
        return got.reshape(1, Tb, nkv, -1)

    def body(j, carry):
        m, l, acc = carry
        kb, vb = block(k_leaf, j), block(v_leaf, j)
        rows = j * Tb + jnp.arange(Tb, dtype=jnp.int32)
        pos_k = ring_positions(end, T, rows) if window else rows
        seen = visible(pos_q, pos_k[None], window)[:, None, None]
        s = jnp.where(seen, _scores(q, kb, scale), NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # a block a query sees nothing of adds nothing, whatever m is
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bkgst,btkd->bkgsd", p,
                                       vb.astype(F32))
        return m_new, l, acc

    lead = (1, nkv, nh // nkv, S)
    if sink is None:
        start = (jnp.full(lead + (1,), NEG_INF, F32),
                 jnp.zeros(lead + (1,), F32))
    else:
        start = (jnp.broadcast_to(sink_rows(sink, nkv), lead + (1,)),
                 jnp.ones(lead + (1,), F32))
    m, l, acc = lax.fori_loop(0, blocks, body,
                              start + (jnp.zeros(lead + (Dv,), F32),))
    out = acc / jnp.where(l > 0, l, 1.0)
    return jnp.moveaxis(out, 3, 1).reshape(1, S, nh, Dv).astype(q.dtype)


def ring_write(leaf, new, pos, row, slot=None):
    """Stacked ring leaf [layers, slots, ring, ...] with ``new`` [B, S, ...]
    written at rows ``(pos + s) mod ring``: of ``slot`` alone (a prefill
    chunk, B == 1), else a row a slot (a decode step, S == 1)."""
    ring = leaf.shape[2]
    new = new.astype(leaf.dtype)
    if slot is None:
        return leaf.at[row, jnp.arange(new.shape[0]), pos % ring].set(
            new[:, 0])
    at = (pos[0] + jnp.arange(new.shape[1], dtype=jnp.int32)) % ring
    return leaf.at[row, jnp.asarray(slot, jnp.int32), at].set(
        new[0], unique_indices=True)


def output_gate(lp, x):
    """``sigmoid(x W_g)`` [B, S, heads x head_dim] float32: what the
    attention's output is multiplied by, elementwise, before ``W_o``."""
    return jax.nn.sigmoid((x @ lp["wg"]).astype(F32))


def ring_attend(q, kw, vw, pos, row, window: int, scale: float,
                impl: str = "auto"):
    """A decode step's q [B, 1, heads, D] (at positions ``pos`` [B], its
    own K/V written) over layer ``row`` of the stacked rings: the ring's
    rows read once, those older than the window masked. Under ``impl``
    "auto" (``kv_cache.attend``'s rule) on a TPU, bfloat16 rows of whole
    lanes go through the stacked flash-decode kernel in its ring form,
    where they lie (it fetches the blocks the window's rows lie in); else
    ("dense", or what the engine fell back to) a masked contraction of the
    layer's block."""
    if impl == "auto" and on_tpu() and kv_cache.plain_decode(q, {"k": kw}):
        from picotron_tpu.ops.pallas.decode_attention import (
            flash_decode_stacked,
        )

        return flash_decode_stacked(q, kw, vw, pos + 1, scale, row,
                                    window=window)
    seen = visible(pos[:, None], ring_positions(pos, kw.shape[2]), window)
    return masked_attention(
        q, lax.dynamic_index_in_dim(kw, row, 0, False),
        lax.dynamic_index_in_dim(vw, row, 0, False), seen, scale)


def attention(lp, x, cos, sin, cfg: Config, cache, pos, row, live,
              window: bool, return_kv: bool):
    """The gated attention on the normed stream ``x`` [B, S, H]: (output
    [B, S, H], the cache leaves it wrote (or the rows a one-shot prefill
    would), keys the live queries attended, keys up to them)."""
    m = cfg.model
    B, S, _ = x.shape
    hd, W = m.head_dim, m.sliding_window if window else 0
    scale = hd ** -0.5
    names = ("kw", "vw") if window else ("k", "v")
    q = (x @ lp["wq"]).reshape(B, S, m.num_attention_heads, hd)
    k = (x @ lp["wk"]).reshape(B, S, m.num_key_value_heads, hd)
    v = (x @ lp["wv"]).reshape(B, S, m.num_key_value_heads, hd)
    q = rms_norm(q, lp["q_norm"], m.rms_norm_eps)
    k = rms_norm(k, lp["k_norm"], m.rms_norm_eps)
    if (WINDOW if window else FULL) in ROTATED:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    steps = jnp.arange(S, dtype=jnp.int32)[None, :]
    with jax.named_scope("afmoe/window_attend" if window
                         else "afmoe/full_attend"):
        if cache is None:
            # a whole sequence from position 0, nothing cached
            pos_q = jnp.broadcast_to(steps, (B, S))
            a = masked_attention(q, k, v, visible(pos_q, pos_q, W), scale)
            # a ring takes the rows a one-shot prompt can fill: those of a
            # chunk (longer prompts go in chunks), which every ring holds
            keep = cfg.inference.prefill_chunk if window else S
            out = {n: r[:, :keep] for n, r in zip(names, (k, v))} \
                if return_kv else {}
        else:
            pos_q = pos[:, None] + steps
            out = {n: cache[n] for n in ("k", "v", "kw", "vw")}
            slot = cache.get("slot")
            if window:
                out.update({n: ring_write(cache[n], new, pos, row, slot)
                            for n, new in zip(names, (k, v))})
            else:
                meta = {} if slot is None else {"slot": slot}
                wrote = kv_cache.cache_write(
                    {"k": cache["k"], "v": cache["v"], **meta}, k, v, pos,
                    row)
                out.update(k=wrote["k"], v=wrote["v"])
            if slot is not None:
                # one slot's chunk: its strip walked in blocks of keys
                a = chunk_attention(q, out[names[0]], out[names[1]], row,
                                    jnp.asarray(slot, jnp.int32), pos_q, W,
                                    scale)
            elif window:
                a = ring_attend(q, out["kw"], out["vw"], pos, row, W, scale,
                                impl=cfg.inference.attend_impl)
            else:
                a = kv_cache.attend(q, {"k": out["k"], "v": out["v"]},
                                    pos + S, scale, row,
                                    impl=cfg.inference.attend_impl)
    a = (a.reshape(B, S, -1).astype(F32) * output_gate(lp, x)).astype(
        x.dtype) @ lp["wo"]
    context = jnp.where(live, pos_q + 1, 0)
    attended = jnp.minimum(context, W) if window else context
    return a, out, jnp.sum(attended, dtype=jnp.int32), \
        jnp.sum(context, dtype=jnp.int32)


# --------------------------------------------------------------------------- #
# experts
# --------------------------------------------------------------------------- #


def expert_mlp(lp, x, m: ModelConfig, live) -> tuple:
    """The expert half of a layer on the normed stream ``x`` [B, S, H]:
    (this chip's part of the routed sum + the shared expert, what
    ``models/experts.py::share`` counted). Rows that are not ``live`` are
    routed nowhere."""
    B, S, H = x.shape
    x2 = x.reshape(B * S, H)
    with jax.named_scope("afmoe/router"):
        logits = jnp.dot(x2.astype(F32), lp["router"].astype(F32),
                         precision=HIGHEST)
        experts, weights = expert_share.route(
            jax.nn.sigmoid(logits), lp["router_bias"],
            k=m.num_experts_per_tok, scale=m.route_scale, eps=ROUTE_EPS)
        w_held = expert_share.held_weights(
            experts, weights, m.ep_rank * m.num_experts, m.num_experts) \
            * live.reshape(B * S, 1).astype(F32)
    y, counted = expert_share.share(lp, x2, w_held)
    return y.reshape(B, S, H), counted


# --------------------------------------------------------------------------- #
# the layer
# --------------------------------------------------------------------------- #


def _layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
           return_kv: bool = False, layer=None, live=None, *, dense: bool,
           window: bool, first: int = 0, kind_first: int = 0):
    """A layer: the attention (``window``: sliding, else full), then a
    SwiGLU (``dense``) or the experts, each branch normed before it joins
    the stream. ``llama.decoder_layer``'s contract; the returned dict also
    holds ``STATS``. Three shapes of call: no cache (a whole sequence; with
    ``return_kv`` its K/V rows under its kind's leaf names), a ``slot`` entry
    (a prefill chunk of that slot), neither (a decode step of every slot)."""
    m = cfg.model
    eps = m.rms_norm_eps
    live = live_rows(cache, live, h)
    row = None if cache is None else leaf_row(layer, first, kind_first)
    a, out, attended, context = attention(
        lp, rms_norm(h, lp["attn_norm"], eps), cos, sin, cfg, cache, pos,
        row, live, window, return_kv)
    h = h + rms_norm(a, lp["post_attn_norm"], eps)
    x = rms_norm(h, lp["mlp_norm"], eps)
    zero = jnp.zeros((), jnp.int32)
    if dense:
        y = expert_share.swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])
        moe = (zero,) * len(expert_share.STAT_NAMES)
    else:
        y, moe = expert_mlp(lp, x, m, live)
    h = h + rms_norm(y, lp["post_mlp_norm"], eps)
    decode = cache is not None and "slot" not in cache
    swa = ((attended, context, zero + int(decode)) if window
           else (zero, zero, zero))
    out[STATS] = jnp.stack(moe + swa)
    return h, out
