"""The Nemotron-H block (``model_type: "nemotron_h"``; Nemotron 3 Super) as
pure functions over a parameter pytree: a layer is ONE sublayer, Mamba-2
(``M``), LatentMoE experts (``E``) or NoPE attention (``*``), in the order
``hybrid_override_pattern`` gives. Serving path only (``Config.validate``
refuses the rest by name).

The equations (``x = RMSNorm(h)``, eps ``rms_norm_eps``, the published
``layer_norm_epsilon``; no bias anywhere but the conv's):

- stream: ``h = E[tokens]``; every layer ``h <- h + sublayer(RMSNorm(h))``,
  the sublayer by the layer's letter; out: ``logits = RMSNorm_f(h) W_head``
  (untied);
- ``M``, Mamba-2 (``d_inner = mamba_num_heads * mamba_head_dim``, ``G =
  n_groups``, ``N = ssm_state_size``, conv ``conv_kernel``): ``[z | u | dt] =
  x W_in`` (``d_inner | d_inner + 2 G N | heads``); ``u_t <- silu(b + sum_j
  w[:, j] u_{t-3+j})`` (causal, depthwise, zeros before the sequence); ``[x_s
  | B | C] = u`` with ``B``, ``C`` [G, N]; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)`` a head; the state ``S[h]`` [head_dim, N] in float32:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g(h)]``, ``y_t = S_t
  C_t[g(h)] + D x_t``, ``g(h) = h // (heads / G)``; ``y <- w *
  RMSNorm_group(y * silu(z))``, the mean square taken over each group's
  ``d_inner / G`` channels (gate first, then the norm); ``W_out``;
- ``*``, attention (no position embedding: the family's attention rotates
  nothing, its Mamba layers carry position): ``q = x W_q``, ``k, v = x W_k, x
  W_v`` (GQA), causal softmax of ``q k^T / sqrt(head_dim)``, ``W_o``;
- ``E``, LatentMoE: ``s = sigmoid(x W_r)`` in float32 over the router's whole
  width (``n_routed_experts * ep_size``); the ``num_experts_per_tok`` largest
  of ``s + b`` (ties to the lower index); weights ``= s[chosen] / (sum +
  1e-20) * routed_scaling_factor``; ``l = x W_down`` (``moe_latent_size``
  wide); ``E_e(l) = relu(l W1_e)^2 W2_e``; ``out = (sum_e w_e E_e(l)) W_up +
  relu(x Ws_1)^2 Ws_2``, the shared expert on the stream. This chip holds
  ``n_routed_experts`` of the experts (``ep_rank * n_routed_experts`` onward)
  and adds their part and the shared expert's (``models/experts.py``: the
  two-matrix form, ``routed_in``/``routed_out``); what the absent experts
  would add is left out before ``W_up``, which is linear, so the chips'
  shares still add up. No token is ever dropped.

How the layers stack. Every other block here pairs a mixer with a
feed-forward part in each layer, and ``models.runs`` groups equal
neighbours; this pattern's runs are all one layer long, and a scan a layer
would compile one body a layer. ``stacking`` cuts the pattern into stretches
that repeat a UNIT of distinct letters (``EMEMEMEMEM*``: five ``EM``, one
``*``), ``layer_groups`` gives one stacked group a stretch, and the group's
layer function runs the unit's sublayers one after the other: what the
engine's scan counts as a layer is a unit (three bodies compile, not
eleven), and a sublayer finds its row of its own kind's cache leaves from
the unit's index (a unit holds a kind at most once).

Prefill runs a Mamba layer as the chunked scan in matmul form
(``ops/ssm.py``: ``ssm_scan``, B and C a group), decode as the one-step
recurrence (``ssm_step``). The state has no token axis: ``dt = 0`` where a
row is not ``live`` freezes ``S`` exactly, the conv tail is taken behind the
last live row, and the first chunk of a prompt (``pos == 0``) starts from
zeros whatever the slot held (as ``models/granite_hybrid.py``).

Every unit returns, beside the updated cache leaves, what its sublayers
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
                                 llama, served_whole, state_counts, support)
from picotron_tpu.models import experts as expert_share
from picotron_tpu.models import mamba2
from picotron_tpu.models.llama import param_bytes  # noqa: F401 - the seam
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.ssm import ssm_scan, ssm_step

# what a unit counts, in the order of the vector (under ``STATS``): the
# expert share's (``experts.STAT_NAMES``), live slot-layers a decode step
# advanced, Mamba layers decode steps ran, live tokens through a prefill scan
# (a layer): the names ``granite_hybrid`` counts under
STAT_NAMES = expert_share.STAT_NAMES + (
    "ssm_state_updates", "ssm_layer_steps", "ssm_tokens_scanned")
N_MOE = len(expert_share.STAT_NAMES)

UNSLICED = expert_share.UNSLICED
# the state has no token axis and cannot be fed a token twice: the engine
# holds the window to whole prefill chunks
CARRIES_STATE = True
F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
ROUTE_EPS = 1e-20
KINDS = "ME*"
TAG = {"M": "m", "E": "e", "*": "a"}  # a kind in a group's name
LEAVES = ("k", "v", "ssm", "conv")  # the cache's, beside "lengths"
WHY = support.RECURRENT_STATE  # what the block cannot do yet


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name (``Config.validate`` calls it)."""
    m = cfg.model
    support.refuse(cfg, for_training, WHY)
    if m.num_nextn_predict_layers > 0:
        raise ValueError(
            f"{support.who(m)} does not support speculation "
            f"(model.num_nextn_predict_layers {m.num_nextn_predict_layers}): "
            f"{WHY['speculation']}, so the multi-token-prediction layer is "
            "not held")
    support.positive(
        m, "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
        "conv_kernel", "n_groups", "chunk_size", "n_routed_experts",
        "num_experts_per_tok", "moe_intermediate_size", "moe_latent_size",
        "moe_shared_expert_intermediate_size", "ep_size")
    support.layer_kinds(
        m, "hybrid_override_pattern", tuple(KINDS), need=("M", "*"),
        note="; '-', a dense MLP layer, is not implemented")
    support.check(m, (
        m.mamba_num_heads % m.n_groups,
        f"mamba_num_heads {m.mamba_num_heads} must be a multiple of "
        f"n_groups {m.n_groups}"))
    support.ep_share(m, "n_routed_experts")
    width = router_width(m)
    support.check(m, (
        width % m.n_group or not 1 <= m.topk_group <= m.n_group,
        f"n_group {m.n_group} must divide the router's width {width} and "
        f"hold topk_group {m.topk_group}"))
    support.pinned(m, use_conv_bias=True, mamba_proj_bias=False,
                   mlp_hidden_act="relu2", n_shared_experts=1,
                   norm_topk_prob=True, tie_word_embeddings=False)


# --------------------------------------------------------------------------- #
# shapes, groups, parameters
# --------------------------------------------------------------------------- #


def d_inner(m: ModelConfig) -> int:
    return m.mamba_num_heads * m.mamba_head_dim


def conv_width(m: ModelConfig) -> int:
    """Channels the conv runs over: ``x_s``, ``B`` and ``C`` of every group."""
    return d_inner(m) + 2 * m.n_groups * m.ssm_state_size


def router_width(m: ModelConfig) -> int:
    return m.n_routed_experts * m.ep_size


def stacking(pattern: str) -> list:
    """[(unit, first layer, repeats)]: ``pattern`` cut, left to right, into
    stretches that repeat a unit of distinct letters, each stretch the one
    that covers the most layers from where it starts (of equals the shorter
    unit). Laid end to end the stretches are the pattern, whatever it is."""
    out, i = [], 0
    while i < len(pattern):
        best = (0, "", 0)
        for u in range(1, len(KINDS) + 1):
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


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    units)]: one group a stretch of ``stacking``, scanned in turn. Each
    function knows its unit, where its stretch begins among all units and,
    of each kind, how many sublayers lie before it."""
    groups, seen, first = [], dict.fromkeys(KINDS, 0), 0
    for i, (unit, _, n) in enumerate(stacking(m.hybrid_override_pattern)):
        name = "".join(TAG[k] for k in unit) + f"_{i}"
        groups.append((name, partial(unit_layers, unit=unit, first=first,
                                     kind_first=dict(seen)), n))
        for k in unit:
            seen[k] += n
        first += n
    return groups


def kind_counts(m: ModelConfig) -> dict:
    return {k: m.hybrid_override_pattern.count(k) for k in KINDS}


def _shapes(m: ModelConfig, kind: str) -> dict:
    """Matmul leaves of a sublayer, (in, out) like every weight here."""
    H, hd = m.hidden_size, m.head_dim
    if kind == "M":
        return {"in_proj": (H, d_inner(m) + conv_width(m)
                            + m.mamba_num_heads),
                "out_proj": (d_inner(m), H)}
    if kind == "*":
        return {"wq": (H, m.num_attention_heads * hd),
                "wk": (H, m.num_key_value_heads * hd),
                "wv": (H, m.num_key_value_heads * hd),
                "wo": (m.num_attention_heads * hd, H)}
    L, I, E = m.moe_latent_size, m.moe_intermediate_size, m.n_routed_experts
    Is = m.moe_shared_expert_intermediate_size
    return {"router": (H, router_width(m)),
            "latent_down": (H, L), "latent_up": (L, H),
            "w1": (E, L, I), "w2": (E, I, L),
            "ws_up": (H, Is), "ws_down": (Is, H)}


# Seeded weights are drawn so that each mechanism of the block is loud
# enough in the logits for a comparison to see a fault in it (as
# ``granite_hybrid.INIT_GAIN``; PERF.md section 6, PR 56, has the controls'
# readings). ``in_proj``'s B and C columns are drawn ``BC`` times wider (with
# the flat draw the state's read-out is a fiftieth of the skip ``D x`` beside
# it); ``wo`` wider (a flat softmax over 1,500 keys is a mean, a fortieth of
# the stream); ``w1``/``ws_up`` wider (relu^2 of a flat draw is a thirtieth of
# the stream) and the routed experts' ``w2`` narrower: 22 of 512 sigmoid
# scores lie a hundredth apart where the choice ends, bfloat16's rounding
# of the stream moves a router logit by a sixth of that, and a choice that
# flips (no fault) swaps one expert's whole output: drawn as loud as the
# shared expert one flip moved a logit by 10 % of the largest (my chip run,
# PR 56), drawn so it moves one by under 1 %.
INIT_GAIN = {"wo": 16.0, "w1": 2.0, "w2": 0.4, "ws_up": 1.5}
BC_GAIN = 6.0
ROUTER_BIAS = 0.02  # the correction bias's draw, U(+-): small, and not zero


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key``: linear weights U(+-gain *
    sqrt(1 / fan_in)) drawn in the model's dtype, norm weights ones, the
    router's correction bias U(+-``ROUTER_BIAS``) in float32, conv taps
    U(+-sqrt(1 / conv_kernel)) with a small bias, ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of dt log-uniform in [1e-3, 1e-1], ``D
    = 1`` (the three in float32). The expert stacks are drawn a unit at a
    time (a stack of gigabytes drawn whole holds its random bits beside
    it)."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("nemotron_h is served on one stage (pp_size 1)")
    dt = jnp.dtype(m.dtype)
    H, W, Di = m.hidden_size, conv_width(m), d_inner(m)

    def uniform(k, shape, fan_in, gain=1.0, dtype=dt):
        bound = gain * math.sqrt(1.0 / fan_in)
        return jax.random.uniform(k, shape, dtype, -bound, bound)

    def sublayer(skey, n: int, kind: str) -> dict:
        out = {TAG[kind] + "_norm": jnp.ones((n, H), dt)}
        shapes = sorted(_shapes(m, kind).items())
        for i, (name, shape) in enumerate(shapes):
            k, gain = jax.random.fold_in(skey, i), INIT_GAIN.get(name, 1.0)
            if name in UNSLICED:
                out[name] = lax.map(
                    lambda kk: uniform(kk, shape, shape[-2], gain),
                    jax.random.split(k, n))
            else:
                out[name] = uniform(k, (n,) + shape, shape[-2], gain)
        ks = [jax.random.fold_in(skey, len(shapes) + j) for j in range(4)]
        if kind == "E":
            out["router_bias"] = jax.random.uniform(
                ks[0], (n, router_width(m)), F32, -ROUTER_BIAS, ROUTER_BIAS)
        if kind == "M":
            out["in_proj"] = mamba2.louder_bc(out["in_proj"], Di, W, BC_GAIN)
            out["gate_norm"] = jnp.ones((n, Di), dt)
            out.update(mamba2.draw(ks, n, heads=m.mamba_num_heads, width=W,
                                   d_conv=m.conv_kernel, dtype=dt))
        return out

    params = {
        "embed": jax.random.normal(jax.random.fold_in(key, 0),
                                   (m.vocab_size, H), F32).astype(dt),
        "final_norm": jnp.ones((H,), dt),
        "lm_head": uniform(jax.random.fold_in(key, 1), (H, m.vocab_size), H),
    }
    for i, ((unit, _, n), (name, _, _)) in enumerate(zip(
            stacking(m.hybrid_override_pattern), layer_groups(m))):
        gkey = jax.random.fold_in(key, 2 + i)
        params[name] = {}
        for j, kind in enumerate(unit):
            params[name].update(sublayer(jax.random.fold_in(gkey, j), n,
                                         kind))
    return params


param_pspecs, num_params, cache_pspecs = served_whole(
    "nemotron_h", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# into and out of the stream; serving state
# --------------------------------------------------------------------------- #


embed_lookup = llama.embed_lookup  # no multiplier
head_logits = llama.head_logits  # final norm, then the untied head


def serving_rope_tables(m: ModelConfig, seq_len: int, dtype) -> tuple:
    """No position embedding: tables nothing reads, of the window's
    length (the programs slice and gather them by position)."""
    t = jnp.zeros((seq_len, 2), dtype)
    return t, t


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1) -> dict:
    """Zeroed cache for ``slots`` sequences, three kinds of leaf, each over
    the layers of its own kind: ``k``/``v`` [attention layers, slots, T, kv
    heads, head_dim] (two heads of 128: the compiler tiles the minor pair
    (2, 128), unpadded, and no program copies the leaf; laid side by side
    in one 256-lane row it padded the row's axis of 1 to 2 and copied the
    leaf whole on its way into every program: tests/test_chip_compile.py);
    ``ssm`` [Mamba layers, slots,
    heads, head_dim, state] float32; ``conv`` [Mamba layers, slots,
    conv_kernel - 1, conv width], the last inputs of the conv."""
    assert not quantized and tp == 1
    dt = jnp.dtype(dtype if dtype is not None else m.dtype)
    n = kind_counts(m)
    kv = (n["*"], slots, max_seq_len, m.num_key_value_heads, m.head_dim)
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "ssm": jnp.zeros((n["M"], slots, m.mamba_num_heads, m.mamba_head_dim,
                          m.ssm_state_size), F32),
        "conv": jnp.zeros((n["M"], slots, m.conv_kernel - 1, conv_width(m)),
                          dt),
        "lengths": jnp.zeros((slots,), jnp.int32),
    }


# --------------------------------------------------------------------------- #
# the three sublayers: (what is added to the stream, cache leaves, counters)
# --------------------------------------------------------------------------- #


def mamba_mixer(lp, x, conv_in, ssm_in, live, m: ModelConfig,
                one_step: tuple) -> tuple:
    """``mamba2.mixer`` at this block's keys: ``B`` and ``C`` a group of
    heads, the gated norm's mean square over each group's channels."""
    return mamba2.mixer(
        lp, x, conv_in, ssm_in, live, one_step, heads=m.mamba_num_heads,
        d_head=m.mamba_head_dim, d_state=m.ssm_state_size,
        d_conv=m.conv_kernel, groups=m.n_groups, chunk=m.chunk_size,
        eps=m.rms_norm_eps, scan=ssm_scan,
        step=ssm_step, norm=rms_norm, scope="nemotron/")


def mamba_sublayer(lp, h, cfg: Config, cache, out: dict, pos, return_kv,
                   row, live) -> tuple:
    """Three shapes of call: no cache (a whole sequence from zeros: the
    state and conv tail behind its last live row are returned as a one-slot
    block), a ``slot`` entry (a prefill chunk carries that slot's state on,
    from zeros where ``pos`` is 0), neither (a decode step advances every
    live slot)."""
    m = cfg.model
    x = rms_norm(h, lp["m_norm"], m.rms_norm_eps)
    y, new, decode = carry_state(
        cache, out, ("conv", "ssm"),
        (((m.conv_kernel - 1, conv_width(m)), h.dtype),
         ((m.mamba_num_heads, m.mamba_head_dim, m.ssm_state_size), F32)),
        row, pos, h, lambda conv_in, ssm_in, step: mamba_mixer(
            lp, x, conv_in, ssm_in, live, m, one_step=step))
    if cache is not None or return_kv:
        out.update(new)
    zero = jnp.zeros((), jnp.int32)
    return y, out, (zero,) * N_MOE + state_counts(live, decode)


def attention_sublayer(lp, h, cfg: Config, cache, out: dict, pos, return_kv,
                       row, live) -> tuple:
    """NoPE attention (GQA, no rotation, scores over ``sqrt(head_dim)``).
    K/V go through ``kv_cache.cache_write`` / ``attend`` at this layer's row
    of the ``k``/``v`` leaves."""
    m = cfg.model
    B, S, _ = h.shape
    hd = m.head_dim
    scale = hd ** -0.5
    with jax.named_scope("nemotron/attend"):
        x = rms_norm(h, lp["a_norm"], m.rms_norm_eps)
        q = (x @ lp["wq"]).reshape(B, S, m.num_attention_heads, hd)
        k = (x @ lp["wk"]).reshape(B, S, m.num_key_value_heads, hd)
        v = (x @ lp["wv"]).reshape(B, S, m.num_key_value_heads, hd)
        if cache is None:
            a = kv_cache.decode_attention(
                q, k, v, jnp.full((B,), S, jnp.int32), scale)
            if return_kv:
                out.update(k=k, v=v)
        else:
            out = kv_cache.cache_write(out, k, v, pos, row)
            a = kv_cache.attend(q, out, pos + S, scale, row,
                                impl=cfg.inference.attend_impl)
        a = a.reshape(B, S, -1) @ lp["wo"]
    return a, out, (jnp.zeros((), jnp.int32),) * len(STAT_NAMES)


def expert_sublayer(lp, h, cfg: Config, cache, out: dict, pos, return_kv,
                    row, live) -> tuple:
    """LatentMoE on the normed stream: the router and the shared expert
    read it, the routed experts its latent; (this chip's part of the routed
    sum, back through ``W_up``, + the shared expert; what
    ``models/experts.py::share`` counted). Rows that are not ``live`` are
    routed nowhere."""
    m = cfg.model
    B, S, H = h.shape
    x2 = rms_norm(h, lp["e_norm"], m.rms_norm_eps).reshape(B * S, H)
    with jax.named_scope("nemotron/router"):
        logits = jnp.dot(x2.astype(F32), lp["router"].astype(F32),
                         precision=HIGHEST)
        experts, weights = expert_share.route(
            jax.nn.sigmoid(logits), lp["router_bias"],
            k=m.num_experts_per_tok, n_group=m.n_group,
            topk_group=m.topk_group, scale=m.routed_scaling_factor,
            eps=ROUTE_EPS)
        w_held = expert_share.held_weights(
            experts, weights, m.ep_rank * m.n_routed_experts,
            m.n_routed_experts) * live.reshape(B * S, 1).astype(F32)
    with jax.named_scope("nemotron/latent_down"):
        latent = x2 @ lp["latent_down"]

    def latent_up(y):
        with jax.named_scope("nemotron/latent_up"):
            return y @ lp["latent_up"]

    y, counted = expert_share.share(lp, x2, w_held, routed_in=latent,
                                    routed_out=latent_up)
    return y.reshape(B, S, H), out, counted + (jnp.zeros((), jnp.int32),) * 3


SUBLAYERS = {"M": mamba_sublayer, "E": expert_sublayer,
             "*": attention_sublayer}


def unit_layers(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                return_kv: bool = False, layer=None, live=None, *,
                unit: str, first: int, kind_first: dict):
    """One unit of a stretch (``stacking``): its sublayers one after the
    other, each ``h <- h + sublayer(norm(h))``. ``llama.decoder_layer``'s
    contract, ``layer`` the unit's index among all units; the returned dict
    also holds ``STATS``, the sum of what the sublayers counted.
    ``cos``/``sin`` are not read."""
    live = live_rows(cache, live, h)
    out = {} if cache is None else {
        n: v for n, v in cache.items() if n not in ("live", "active")}
    stats = jnp.zeros((len(STAT_NAMES),), jnp.int32)
    for kind in unit:
        row = None if cache is None else leaf_row(
            layer, first, kind_first[kind])
        y, out, counted = SUBLAYERS[kind](lp, h, cfg, cache, out, pos,
                                          return_kv, row, live)
        h = h + y
        stats = stats + jnp.stack(counted)
    out[STATS] = stats
    return h, out
