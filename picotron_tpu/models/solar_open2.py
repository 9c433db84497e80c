"""The Solar Open 2 block (``model_type: "solar_open2"``) as pure functions
over a parameter pytree: Kimi-delta-attention (KDA) layers, ``gqa_interval``
of them between two gated NoPE GQA layers (``gqa_layers``), every layer
followed by routed experts and a shared one. Serving path only
(``Config.validate`` refuses the rest by name).

The equations (``x = RMSNorm(h)``, eps ``rms_norm_eps``; no bias anywhere):

- stream: ``h = E[tokens]``; every layer ``h <- h + mixer(RMSNorm_1(h))``,
  then ``h <- h + experts(RMSNorm_2(h))``; ``logits = RMSNorm_f(h) W_head``
  (untied). The mixer is GQA where the layer's index is in ``gqa_layers``,
  KDA elsewhere; ``first_k_dense_replace`` 0: every layer has experts, and
  no layer reads ``intermediate_size``;
- KDA layer (``linear_attn_config``: ``num_heads`` heads of ``head_dim`` for
  keys and values alike, conv ``short_conv_kernel_size``): ``[q' | k' | v] =
  conv(x W_qkv)``, each channel its own causal convolution then SiLU, ``c_t =
  silu(sum_j w[:, j] u_{t-3+j})``, zeros before the sequence (``W_qkv`` is
  ``W_q``, ``W_k`` and ``W_v`` side by side and the three published
  convolutions one over all their channels: depthwise, so the same numbers);
  a head at a time ``q = q' / ||q'|| * head_dim^-0.5``, ``k = k' / ||k'||``
  (eps 1e-6 under the root); the decay ``g = -exp(A_log[h]) * softplus((x
  W_fa) W_fb + dt_bias)`` a head and key channel, float32 (``kda_use_full_proj``:
  one matrix ``W_f`` in the pair's place, and ``W_g`` in the gate's); ``b =
  sigmoid(x W_b)`` a head, doubled under ``kda_allow_neg_eigval``; the state
  ``S[h]`` [keys, values] in float32 through ``ops/kda.py``: ``S' =
  Diag(exp(g_t)) S_{t-1}``, ``S_t = S' + b_t k_t (v_t - S'^T k_t)^T``, ``o_t =
  S_t^T q_t``; ``y = w_o * RMSNorm_head(o_t) * sigmoid((x W_ga) W_gb)``;
  ``W_out``;
- GQA layer (``use_rope`` false: nothing is rotated): ``q = x W_q``, ``k, v =
  x W_k, x W_v``, causal softmax of ``q k^T / sqrt(head_dim)``; under
  ``use_gqa_gate`` ``a <- a * sigmoid(x W_g)``, an entry for an entry
  (``afmoe.output_gate``'s form), before ``W_o``;
- experts: ``s = sigmoid(x W_r)`` in float32 over the router's whole width
  (``n_routed_experts * ep_size``); the ``num_experts_per_tok`` largest of
  ``s + bias`` (no group limit; ties to the lower index); weights ``=
  s[chosen] / (sum + 1e-20) * routed_scaling_factor``; ``E_e(x) = (silu(x
  W1_e) * (x W3_e)) W2_e``; ``out = sum_e w_e E_e(x) + E_s(x)``, one shared
  expert ``moe_intermediate_size * n_shared_experts`` wide. This chip holds
  ``n_routed_experts`` of the experts (``ep_rank * n_routed_experts`` onward)
  and adds their part and the shared expert's (``models/experts.py``); what
  the absent experts would add is left out. No token is ever dropped.

Prefill runs a KDA layer as the chunked form (``ops/kda.py``: ``kda_scan``),
decode as the recurrence written out (``kda_step``). The state has no token
axis, so nothing hides a previous occupant or a row that is not live: ``g =
0`` and ``b = 0`` where a row is not ``live`` freeze ``S`` exactly, the conv
tail is taken behind the last live row, and the first chunk of a prompt
(``pos == 0``) starts from zeros whatever the slot held (as
``models/granite_hybrid.py``).

The tree: one stacked group a run of equal mixers (``layer_groups``),
``kda_<i>`` or ``gqa_<i>``; a layer finds its row of its own kind's cache
leaves from the scan's global index (``models.leaf_row``).

Every layer function returns, beside the updated cache leaves, what it
counted (``STATS``, in the order of ``STAT_NAMES``; docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.inference import kv_cache
from picotron_tpu.models import (STATS, carry_state, leaf_row, live_rows,
                                 llama, runs, served_whole, state_counts,
                                 support)
from picotron_tpu.models import experts as expert_share
from picotron_tpu.models.afmoe import output_gate
from picotron_tpu.models.granite_hybrid import (  # noqa: F401 - the seam
    serving_rope_tables,  # no position embedding: tables nothing reads
)
from picotron_tpu.models.llama import param_bytes  # noqa: F401 - the seam
from picotron_tpu.ops.kda import kda_scan, kda_step
from picotron_tpu.ops.rmsnorm import rms_norm

# what a layer counts, in the order of the vector (under ``STATS``): the
# expert share's (``experts.STAT_NAMES``), live slot-layers a decode step
# advanced, KDA layers decode steps ran, live tokens through a prefill scan
# (a layer)
STAT_NAMES = expert_share.STAT_NAMES + (
    "kda_state_updates", "kda_layer_steps", "kda_tokens_scanned")

UNSLICED = expert_share.UNSLICED
# the state has no token axis and cannot be fed a token twice: the engine
# holds the window to whole prefill chunks
CARRIES_STATE = True
F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
ROUTE_EPS = 1e-20
L2_EPS = 1e-6  # under the root of q's and k's norms
LEAVES = ("k", "v", "kda", "conv")  # the cache's, beside "lengths"
# what the block cannot do yet, and why (``support.refuse``)
WHY = {**support.RECURRENT_STATE,
       "training": "no backward through the chunked delta rule and the "
                   "expert share"}


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name (``Config.validate`` calls it)."""
    m = cfg.model
    who = support.who(m)
    support.refuse(cfg, for_training, WHY)
    support.positive(m, "n_routed_experts", "n_shared_experts",
                     "num_experts_per_tok", "moe_intermediate_size",
                     "ep_size")
    la = m.linear_attn_config or {}
    need = ("num_heads", "head_dim", "short_conv_kernel_size")
    if any(int(la.get(n) or 0) < 1 for n in need) \
            or la.get("num_kv_heads") not in (None, la.get("num_heads")):
        raise ValueError(
            f"{who} needs model.linear_attn_config with "
            f"{', '.join(need)} each >= 1 and num_kv_heads null or "
            f"num_heads (k and v a head each; got "
            f"{m.linear_attn_config!r})")
    gqa = m.gqa_layers or []
    kda = [i for i in range(m.num_hidden_layers) if i not in gqa]
    if not gqa or not kda or sorted(set(gqa)) != list(gqa) \
            or not 0 <= gqa[0] <= gqa[-1] < m.num_hidden_layers:
        raise ValueError(
            f"{who} needs model.gqa_layers: rising indices among the "
            f"{m.num_hidden_layers} layers held, with at least one GQA "
            f"and one KDA layer (the cache holds a leaf of each kind; "
            f"got {m.gqa_layers!r})")
    support.check(m, (
        m.gqa_interval and any(
            b - a != m.gqa_interval + 1 for a, b in zip(gqa, gqa[1:])),
        f"gqa_layers {gqa} do not lie gqa_interval {m.gqa_interval} KDA "
        "layers apart"))
    support.ep_share(m, "n_routed_experts")
    support.pinned(m, use_rope=False, first_k_dense_replace=0,
                   scoring_func="sigmoid", norm_topk_prob=True,
                   tie_word_embeddings=False)


# --------------------------------------------------------------------------- #
# shapes, groups, parameters
# --------------------------------------------------------------------------- #


def kda_dims(m: ModelConfig) -> tuple:
    """(heads, a head's width for keys and values alike, conv taps)."""
    la = m.linear_attn_config
    return (int(la["num_heads"]), int(la["head_dim"]),
            int(la["short_conv_kernel_size"]))


def conv_width(m: ModelConfig) -> int:
    """Channels the conv runs over: ``q'``, ``k'`` and ``v``."""
    nh, hd, _ = kda_dims(m)
    return 3 * nh * hd


def router_width(m: ModelConfig) -> int:
    return m.n_routed_experts * m.ep_size


def mixers(m: ModelConfig) -> list:
    """Each held layer's mixer, "gqa" or "kda"."""
    gqa = set(m.gqa_layers)
    return ["gqa" if i in gqa else "kda" for i in range(m.num_hidden_layers)]


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    layers)]: one group a run of equal mixers, scanned in turn. Each
    function knows where its run begins, among all layers and among those
    of its kind."""
    fns = {"kda": kda_layer, "gqa": gqa_layer}
    return [(f"{kind}_{i}", partial(fns[kind], first=first, kind_first=kf), n)
            for i, (kind, first, kf, n) in enumerate(runs(mixers(m)))]


def kind_counts(m: ModelConfig) -> dict:
    kinds = mixers(m)
    return {k: kinds.count(k) for k in ("kda", "gqa")}


def _mixer_shapes(m: ModelConfig, kind: str) -> dict:
    """Matmul leaves of a layer's mixer, (in, out) like every weight here."""
    H = m.hidden_size
    if kind == "gqa":
        q, kv = m.num_attention_heads * m.head_dim, \
            m.num_key_value_heads * m.head_dim
        gate = {"wg": (H, q)} if m.use_gqa_gate else {}
        return {"wq": (H, q), "wk": (H, kv), "wv": (H, kv), **gate,
                "wo": (q, H)}
    nh, hd, _ = kda_dims(m)
    # the decay's and the gate's way to a head's width: through a rank of
    # ``head_dim``, or (``kda_use_full_proj``) one matrix
    narrow = ({"w_f": (H, nh * hd), "w_g": (H, nh * hd)}
              if m.kda_use_full_proj else
              {"w_fa": (H, hd), "w_fb": (hd, nh * hd),
               "w_ga": (H, hd), "w_gb": (hd, nh * hd)})
    return {"wqkv": (H, conv_width(m)), "w_b": (H, nh), **narrow,
            "wo": (nh * hd, H)}


def _expert_shapes(m: ModelConfig) -> dict:
    H, I, E = m.hidden_size, m.moe_intermediate_size, m.n_routed_experts
    Is = I * m.n_shared_experts
    return {"router": (H, router_width(m)),
            "w1": (E, H, I), "w3": (E, H, I), "w2": (E, I, H),
            "ws_gate": (H, Is), "ws_up": (H, Is), "ws_down": (Is, H)}


# Seeded weights are drawn so that each mechanism of the block is loud
# enough in the logits for a comparison to see a fault in it (as
# ``granite_hybrid.INIT_GAIN``; PERF.md section 6, PR 58, has the controls'
# readings). The GQA layers' ``wo`` wider: a flat softmax over 1,500 keys is
# a mean, a fortieth of the stream. The KDA layers' ``wo`` wider too: the
# head's norm brings the read-out to unit size whatever the state holds, the
# gate halves it, and ``wo`` at the flat draw makes a third of the stream of
# it. The routed experts' ``w2`` narrower: where the choice of 8 of 320 ends
# the scores lie a hundredth apart, and a choice that bfloat16's rounding of
# the stream flips (no fault) swaps one expert's whole output.
INIT_GAIN = {"gqa": {"wo": 16.0, "w2": 0.5}, "kda": {"wo": 2.0, "w2": 0.5}}
ROUTER_BIAS = 0.02  # the correction bias's draw, U(+-): small, and not zero


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key``: linear weights U(+-gain *
    sqrt(1 / fan_in)) drawn in the model's dtype, norm weights ones, the
    router's correction bias U(+-``ROUTER_BIAS``) in float32, conv taps
    U(+-sqrt(1 / taps)), ``A_log = log U(1, 16)`` a head, ``dt_bias`` the
    inverse softplus of dt log-uniform in [1e-3, 1e-1] a head and key
    channel (the two in float32). The expert stacks are drawn a layer at a
    time (a stack of gigabytes drawn whole holds its random bits beside
    it)."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("solar_open2 is served on one stage (pp_size 1)")
    dt = jnp.dtype(m.dtype)
    H = m.hidden_size
    nh, hd, taps = kda_dims(m)

    def uniform(k, shape, fan_in, gain=1.0, dtype=dt):
        bound = gain * math.sqrt(1.0 / fan_in)
        return jax.random.uniform(k, shape, dtype, -bound, bound)

    def group(gkey, n: int, kind: str) -> dict:
        ones = lambda w: jnp.ones((n, w), dt)
        out = {"mixer_norm": ones(H), "mlp_norm": ones(H)}
        shapes = sorted({**_mixer_shapes(m, kind),
                         **_expert_shapes(m)}.items())
        for i, (name, shape) in enumerate(shapes):
            k, gain = jax.random.fold_in(gkey, i), \
                INIT_GAIN[kind].get(name, 1.0)
            if name in UNSLICED:
                out[name] = lax.map(
                    lambda kk: uniform(kk, shape, shape[-2], gain),
                    jax.random.split(k, n))
            else:
                out[name] = uniform(k, (n,) + shape, shape[-2], gain)
        ks = [jax.random.fold_in(gkey, len(shapes) + j) for j in range(4)]
        out["router_bias"] = jax.random.uniform(
            ks[0], (n, router_width(m)), F32, -ROUTER_BIAS, ROUTER_BIAS)
        if kind == "kda":
            out["o_norm"] = ones(hd)
            out["conv_w"] = uniform(ks[1], (n, conv_width(m), taps), taps)
            out["A_log"] = jnp.log(jax.random.uniform(
                ks[2], (n, nh), F32, 1.0, 16.0))
            step = jnp.exp(jax.random.uniform(
                ks[3], (n, nh * hd), F32, math.log(1e-3), math.log(1e-1)))
            out["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
        return out

    params = {
        "embed": jax.random.normal(jax.random.fold_in(key, 0),
                                   (m.vocab_size, H), F32).astype(dt),
        "final_norm": jnp.ones((H,), dt),
        "lm_head": uniform(jax.random.fold_in(key, 1), (H, m.vocab_size), H),
    }
    for i, (name, _, n) in enumerate(layer_groups(m)):
        params[name] = group(jax.random.fold_in(key, 2 + i), n,
                             name.split("_")[0])
    return params


param_pspecs, num_params, cache_pspecs = served_whole(
    "solar_open2", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# into and out of the stream; serving state
# --------------------------------------------------------------------------- #


embed_lookup = llama.embed_lookup  # no multiplier
head_logits = llama.head_logits  # final norm, then the untied head


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1) -> dict:
    """Zeroed cache for ``slots`` sequences, three kinds of leaf, each over
    the layers of its own kind: ``k``/``v`` [GQA layers, slots, T, kv heads,
    head_dim]; ``kda`` [KDA layers, slots, heads, keys, values] float32;
    ``conv`` [KDA layers, slots, taps - 1, conv width], the last inputs of
    the conv."""
    assert not quantized and tp == 1
    dt = jnp.dtype(dtype if dtype is not None else m.dtype)
    n = kind_counts(m)
    nh, hd, taps = kda_dims(m)
    kv = (n["gqa"], slots, max_seq_len, m.num_key_value_heads, m.head_dim)
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "kda": jnp.zeros((n["kda"], slots, nh, hd, hd), F32),
        "conv": jnp.zeros((n["kda"], slots, taps - 1, conv_width(m)), dt),
        "lengths": jnp.zeros((slots,), jnp.int32),
    }


# --------------------------------------------------------------------------- #
# the KDA mixer
# --------------------------------------------------------------------------- #


def l2_normalise(x):
    """A head's vector over its own length, float32."""
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _narrow(lp, x, full: str, a: str, b: str):
    """``x`` to a head's width: one matrix, or through the rank."""
    return x @ lp[full] if full in lp else (x @ lp[a]) @ lp[b]


def kda_mixer(lp, x, conv_in, state_in, live, m: ModelConfig,
              one_step: tuple) -> tuple:
    """The mixer on the normed stream ``x`` [B, S, H] from the conv's last
    inputs ``conv_in`` [B, taps - 1, width] and the state ``state_in``:
    (output [B, S, H], the conv's last inputs and the state behind the last
    ``live`` row). ``live`` [B, S] marks the real rows, a leading run of
    each sequence. ``one_step`` is empty, or on a decode step ``(row,)``:
    ``state_in`` is then the whole stacked leaf and so is the state
    returned, that row of it advanced (``ops/kda.py::kda_step``)."""
    B, S, _ = x.shape
    nh, hd, taps = kda_dims(m)
    D = nh * hd
    with jax.named_scope("solar/kda_conv"):
        u = x @ lp["wqkv"]
        padded = jnp.concatenate([conv_in.astype(u.dtype), u], axis=1)
        w = lp["conv_w"].astype(F32)
        conv = sum(padded[:, j:j + S].astype(F32) * w[:, j]
                   for j in range(taps))
        u = jax.nn.silu(conv).astype(x.dtype)
        # the last inputs behind the last live row: rows n .. n + taps - 2
        # of the padded block, n the live rows (0: the tail stays as it was)
        at = jnp.sum(live, axis=1, dtype=jnp.int32)[:, None] \
            + jnp.arange(taps - 1, dtype=jnp.int32)[None, :]
        conv_out = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    heads = lambda a: a.reshape(B, S, nh, hd)
    q = l2_normalise(heads(u[..., :D])) * hd ** -0.5
    k = l2_normalise(heads(u[..., D:2 * D]))
    v = heads(u[..., 2 * D:])
    lives = live[..., None].astype(F32)
    # g 0 and b 0 where a row is not live: the state stays as it is
    g = -jnp.exp(lp["A_log"])[:, None] * heads(jax.nn.softplus(
        _narrow(lp, x, "w_f", "w_fa", "w_fb").astype(F32) + lp["dt_bias"])) \
        * lives[..., None]
    b = jax.nn.sigmoid((x @ lp["w_b"]).astype(F32)) * lives \
        * (2.0 if m.kda_allow_neg_eigval else 1.0)
    if one_step:
        with jax.named_scope("solar/kda_step"):
            o, state_out = kda_step(q, k, v, g, b, state_in, *one_step)
    else:
        with jax.named_scope("solar/kda_scan"):
            o, state_out = kda_scan(q, k, v, g, b, state_in)
    with jax.named_scope("solar/kda_gate"):
        gate = jax.nn.sigmoid(heads(
            _narrow(lp, x, "w_g", "w_ga", "w_gb").astype(F32)))
        y = rms_norm(o, lp["o_norm"], m.rms_norm_eps) * gate
        out = y.reshape(B, S, D).astype(x.dtype) @ lp["wo"]
    return out, conv_out, state_out


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
    with jax.named_scope("solar/router"):
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
# the two kinds of layer
# --------------------------------------------------------------------------- #


def _finish(lp, h, m: ModelConfig, live, out: dict, kda_stats: tuple):
    """The expert half, and the layer's counters beside its cache leaves."""
    y, moe = expert_mlp(
        lp, rms_norm(h, lp["mlp_norm"], m.rms_norm_eps), m, live)
    out[STATS] = jnp.stack(moe + kda_stats)
    return h + y, out


def kda_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
              return_kv: bool = False, layer=None, live=None, *,
              first: int = 0, kind_first: int = 0):
    """A KDA layer, then the experts. ``llama.decoder_layer``'s contract;
    the returned dict also holds ``STATS``. Three shapes of call: no cache
    (a whole sequence from zeros: the state and conv tail behind its last
    live row are returned as a one-slot block), a ``slot`` entry (a prefill
    chunk carries that slot's state on, from zeros where ``pos`` is 0),
    neither (a decode step advances every live slot). ``cos``/``sin`` are
    not read."""
    m = cfg.model
    nh, hd, taps = kda_dims(m)
    live = live_rows(cache, live, h)
    x = rms_norm(h, lp["mixer_norm"], m.rms_norm_eps)
    y, new, decode = carry_state(
        cache, cache, ("conv", "kda"),
        (((taps - 1, conv_width(m)), h.dtype), ((nh, hd, hd), F32)),
        None if cache is None else leaf_row(layer, first, kind_first), pos,
        h, lambda conv_in, state_in, step: kda_mixer(
            lp, x, conv_in, state_in, live, m, one_step=step))
    if cache is None:
        out = new if return_kv else {}
    else:
        out = {n: v for n, v in cache.items() if n not in ("live", "active")}
        out.update(new)
    return _finish(lp, h + y, m, live, out, state_counts(live, decode))


def gqa_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
              return_kv: bool = False, layer=None, live=None, *,
              first: int = 0, kind_first: int = 0):
    """A NoPE attention layer (GQA, no rotation, scores over
    ``sqrt(head_dim)``, its output gated entry for entry under
    ``use_gqa_gate``), then the experts. ``cos``/``sin`` are not read. K/V
    go through ``kv_cache.cache_write`` / ``attend`` at this layer's row of
    the ``k``/``v`` leaves."""
    m = cfg.model
    B, S, _ = h.shape
    hd = m.head_dim
    scale = hd ** -0.5
    live = live_rows(cache, live, h)
    x = rms_norm(h, lp["mixer_norm"], m.rms_norm_eps)
    with jax.named_scope("solar/attend"):
        q = (x @ lp["wq"]).reshape(B, S, m.num_attention_heads, hd)
        k = (x @ lp["wk"]).reshape(B, S, m.num_key_value_heads, hd)
        v = (x @ lp["wv"]).reshape(B, S, m.num_key_value_heads, hd)
        if cache is None:
            a = kv_cache.decode_attention(
                q, k, v, jnp.full((B,), S, jnp.int32), scale)
            out = {"k": k, "v": v} if return_kv else {}
        else:
            row = leaf_row(layer, first, kind_first)
            out = kv_cache.cache_write(
                {n: c for n, c in cache.items()
                 if n not in ("live", "active")}, k, v, pos, row)
            a = kv_cache.attend(q, out, pos + S, scale, row,
                                impl=cfg.inference.attend_impl)
        a = a.reshape(B, S, -1)
    with jax.named_scope("solar/attend_gate"):
        if "wg" in lp:
            a = (a.astype(F32) * output_gate(lp, x)).astype(x.dtype)
        a = a @ lp["wo"]
    zero = jnp.zeros((), jnp.int32)
    return _finish(lp, h + a, m, live, out, (zero, zero, zero))
