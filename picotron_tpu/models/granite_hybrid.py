"""The Granite-4.0-H block (``model_type: "granitemoehybrid"``) as pure
functions over a parameter pytree: Mamba-2 layers and NoPE attention layers
in the order ``layer_types`` gives, each followed by routed experts and a
shared MLP. Serving path only (``Config.validate`` refuses the rest by name).

The equations (``x`` the normed stream; RMSNorm, eps ``rms_norm_eps``; no
bias anywhere but the conv's):

- stream: ``h = E[tokens] * embedding_multiplier``; a layer: ``h +=
  residual_multiplier * mixer(norm(h))``, then ``h += residual_multiplier *
  (experts(norm(h)) + shared(norm(h)))``; out: ``logits = norm(h) E^T /
  logits_scaling`` (the head is the embedding, tied as published);
- attention layer (no position embedding): ``q = x W_q``, ``k, v = x W_k, x
  W_v`` (GQA, no rotation), causal softmax of ``q k^T *
  attention_multiplier`` (not ``head_dim^-0.5``), ``W_o``;
- Mamba-2 layer (``d_inner = mamba_n_heads * mamba_d_head``, one group, state
  ``mamba_d_state``, conv ``mamba_d_conv``): ``[z | u | dt] = x W_in``
  (``d_inner | d_inner + 2 d_state | heads``); ``u_t <- silu(b + sum_j w[:,
  j] u_{t-3+j})`` (causal, depthwise, zeros before the sequence); ``[x_s | B
  | C] = u``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` per head;
  the state ``S[h]`` [d_head, d_state] in float32: ``S_t = exp(dt_t A) S_{t-1}
  + dt_t x_t (x) B_t``; ``y_t = S_t C_t + D x_t``; ``y <- norm(y *
  silu(z))`` over all of ``d_inner`` (gate first, then the norm); ``W_out``;
- experts: ``l = x W_r`` in float32 over the router's whole width
  (``num_local_experts * ep_size``); the ``num_experts_per_tok`` largest
  logits (ties to the lower index), weights = softmax over those; ``E_e(x) =
  (silu(x W1_e) * (x W3_e)) W2_e``; the shared MLP the same at
  ``shared_intermediate_size``. This chip holds ``num_local_experts`` of the
  experts (``ep_rank * num_local_experts`` onward) and adds their part and
  the shared MLP's (``models/experts.py``); what the absent experts would
  add is left out. No token is ever dropped.

Prefill runs a Mamba layer as the chunked scan in matmul form
(``ops/ssm.py``: ``ssm_scan``), decode as the one-step recurrence
(``ssm_step``). The state
has no token axis, so nothing hides a previous occupant or a row that is not
live: ``dt = 0`` where a row is not ``live`` freezes ``S`` exactly (``exp(0)
S + 0``), the conv tail is taken behind the last live row, and the first
chunk of a prompt (``pos == 0``) starts from zeros whatever the slot held.

The tree: one stacked group a run of ``layer_types`` (``layer_groups``),
``mamba_<i>`` or ``attention_<i>``; a layer finds its row of its own kind's
cache leaf from the scan's global index (``models.leaf_row``).

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
from picotron_tpu.models import mamba2
from picotron_tpu.models.llama import param_bytes  # noqa: F401 - the seam
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.ssm import ssm_scan, ssm_step

# what a layer counts, in the order of the vector (under ``STATS``):
# the expert share's (``experts.STAT_NAMES``), live slot-layers a decode
# step advanced, Mamba layers decode steps ran, live tokens through a
# prefill scan (a layer)
STAT_NAMES = expert_share.STAT_NAMES + (
    "ssm_state_updates", "ssm_layer_steps", "ssm_tokens_scanned")

UNSLICED = expert_share.UNSLICED
# the state has no token axis and cannot be fed a token twice: the engine
# holds the window to whole prefill chunks (``prefill_chunked``'s last
# chunk slides back, and re-feeds its overlap, where it would pass the end)
CARRIES_STATE = True
LEAVES = ("k", "v", "ssm", "conv")  # the cache's, beside "lengths"
WHY = support.RECURRENT_STATE  # what the block cannot do yet
F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name (``Config.validate`` calls it)."""
    m = cfg.model
    support.refuse(cfg, for_training, WHY)
    support.positive(
        m, "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_d_conv",
        "mamba_chunk_size", "num_local_experts", "num_experts_per_tok",
        "shared_intermediate_size", "ep_size")
    support.layer_kinds(m, "layer_types", ("mamba", "attention"))
    support.check(m, (
        m.mamba_n_heads * m.mamba_d_head != m.mamba_expand * m.hidden_size,
        f"mamba_n_heads {m.mamba_n_heads} x mamba_d_head {m.mamba_d_head} "
        f"must be mamba_expand {m.mamba_expand} x hidden_size "
        f"{m.hidden_size}"))
    support.ep_share(m, "num_local_experts")
    support.pinned(m, mamba_n_groups=1, mamba_conv_bias=True,
                   mamba_proj_bias=False, position_embedding_type="nope",
                   tie_word_embeddings=True, rope_scaling=None)


# --------------------------------------------------------------------------- #
# shapes, groups, parameters
# --------------------------------------------------------------------------- #


def d_inner(m: ModelConfig) -> int:
    return m.mamba_n_heads * m.mamba_d_head


def conv_width(m: ModelConfig) -> int:
    """Channels the conv runs over: ``x_s``, ``B`` and ``C``."""
    return d_inner(m) + 2 * m.mamba_n_groups * m.mamba_d_state


def router_width(m: ModelConfig) -> int:
    return m.num_local_experts * m.ep_size


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    layers)]: one group a run of ``layer_types``, scanned in turn. Each
    function knows where its run begins, among all layers and among those
    of its kind."""
    fns = {"mamba": mamba_layer, "attention": attention_layer}
    return [(f"{kind}_{i}", partial(fns[kind], first=first, kind_first=kf), n)
            for i, (kind, first, kf, n) in enumerate(runs(m.layer_types))]


def kind_counts(m: ModelConfig) -> dict:
    return {k: sum(t == k for t in m.layer_types)
            for k in ("mamba", "attention")}


def _mixer_shapes(m: ModelConfig, kind: str) -> dict:
    """Matmul leaves of a layer's mixer, (in, out) like every weight here."""
    H = m.hidden_size
    if kind == "mamba":
        return {"in_proj": (H, d_inner(m) + conv_width(m) + m.mamba_n_heads),
                "out_proj": (d_inner(m), H)}
    hd = m.head_dim
    return {"wq": (H, m.num_attention_heads * hd),
            "wk": (H, m.num_key_value_heads * hd),
            "wv": (H, m.num_key_value_heads * hd),
            "wo": (m.num_attention_heads * hd, H)}


def _expert_shapes(m: ModelConfig) -> dict:
    H, I, Is = m.hidden_size, m.intermediate_size, m.shared_intermediate_size
    E = m.num_local_experts
    return {"router": (H, router_width(m)),
            "w1": (E, H, I), "w3": (E, H, I), "w2": (E, I, H),
            "ws_gate": (H, Is), "ws_up": (H, Is), "ws_down": (Is, H)}


# Seeded weights are drawn so that each mechanism of the block is loud
# enough in the logits for a comparison to see a fault in it (as
# ``deepseek_v32.INIT_GAIN``; PERF.md, PR 32, has the readings).
# ``in_proj``'s B and C columns are drawn ``BC`` times wider: with the flat
# draw ``S C`` is a fiftieth of the skip ``D x`` beside it and neither a
# lost state nor a shifted conv tail moves a logit. ``wo`` wider: a flat
# softmax over a thousand keys is a mean, a thirtieth of the stream.
INIT_GAIN = {"wo": 8.0, "w2": 0.5}
BC_GAIN = 6.0
# the embedding's draw: N(0, 1) / (embedding_multiplier * sqrt(H)). The
# head is the embedding, so the stream's part along the token's own row
# comes back as that token's logit, sqrt(H) times louder than the rest: a
# unit-norm entry keeps it among the others.


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key``: linear weights U(+-gain *
    sqrt(1 / fan_in)) drawn in the model's dtype, norm weights ones, conv
    taps U(+-sqrt(1 / d_conv)) with a small bias, ``A_log = log U(1, 16)``,
    ``dt_bias`` the inverse softplus of dt log-uniform in [1e-3, 1e-1], ``D
    = 1`` (the three in float32). No ``lm_head``: the head is ``embed``."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("granitemoehybrid is served on one stage (pp_size 1)")
    dt = jnp.dtype(m.dtype)
    H, W, Di = m.hidden_size, conv_width(m), d_inner(m)

    def uniform(k, shape, fan_in, gain=1.0, dtype=dt):
        bound = gain * math.sqrt(1.0 / fan_in)
        return jax.random.uniform(k, shape, dtype, -bound, bound)

    def group(gkey, n: int, kind: str) -> dict:
        ones = lambda w: jnp.ones((n, w), dt)
        out = {"mixer_norm": ones(H), "mlp_norm": ones(H)}
        shapes = sorted({**_mixer_shapes(m, kind),
                         **_expert_shapes(m)}.items())
        for i, (name, shape) in enumerate(shapes):
            out[name] = uniform(jax.random.fold_in(gkey, i), (n,) + shape,
                                shape[-2], INIT_GAIN.get(name, 1.0))
        if kind == "mamba":
            out["in_proj"] = mamba2.louder_bc(out["in_proj"], Di, W, BC_GAIN)
            out["gate_norm"] = ones(Di)
            out.update(mamba2.draw(
                [jax.random.fold_in(gkey, len(shapes) + j) for j in range(4)],
                n, heads=m.mamba_n_heads, width=W, d_conv=m.mamba_d_conv,
                dtype=dt))
        return out

    params = {
        "embed": (jax.random.normal(jax.random.fold_in(key, 0),
                                    (m.vocab_size, H), F32)
                  / (m.embedding_multiplier * math.sqrt(H))).astype(dt),
        "final_norm": jnp.ones((H,), dt),
    }
    for i, (name, _, n) in enumerate(layer_groups(m)):
        params[name] = group(jax.random.fold_in(key, 2 + i), n,
                             name.split("_")[0])
    return params


param_pspecs, num_params, cache_pspecs = served_whole(
    "granitemoehybrid", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# into and out of the stream; serving state
# --------------------------------------------------------------------------- #


def embed_lookup(w, tokens, cfg: Config):
    """``E[tokens] * embedding_multiplier``, in the embedding's dtype."""
    return llama.embed_lookup(w, tokens) * jnp.asarray(
        cfg.model.embedding_multiplier, w.dtype)


def head_logits(params, h, cfg: Config):
    """Final norm, then the embedding as the head, over ``logits_scaling``."""
    m = cfg.model
    x = rms_norm(h, params["final_norm"], m.rms_norm_eps)
    logits = jnp.einsum("bsh,vh->bsv", x, params["embed"])
    return logits / jnp.asarray(m.logits_scaling, logits.dtype)


def serving_rope_tables(m: ModelConfig, seq_len: int, dtype) -> tuple:
    """No position embedding: tables nothing reads, of the window's
    length (the programs slice and gather them by position)."""
    t = jnp.zeros((seq_len, 2), dtype)
    return t, t


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1) -> dict:
    """Zeroed cache for ``slots`` sequences, three kinds of leaf, each over
    the layers of its own kind: ``k``/``v`` [attention layers, slots, T, kv
    heads, head_dim]; ``ssm`` [Mamba layers, slots, heads, d_head, d_state]
    float32; ``conv`` [Mamba layers, slots, d_conv - 1, conv width], the
    last inputs of the conv."""
    assert not quantized and tp == 1
    dt = jnp.dtype(dtype if dtype is not None else m.dtype)
    n = kind_counts(m)
    kv = (n["attention"], slots, max_seq_len, m.num_key_value_heads,
          m.head_dim)
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "ssm": jnp.zeros((n["mamba"], slots, m.mamba_n_heads, m.mamba_d_head,
                          m.mamba_d_state), F32),
        "conv": jnp.zeros((n["mamba"], slots, m.mamba_d_conv - 1,
                           conv_width(m)), dt),
        "lengths": jnp.zeros((slots,), jnp.int32),
    }


# --------------------------------------------------------------------------- #
# the Mamba-2 mixer
# --------------------------------------------------------------------------- #


def mamba_mixer(lp, x, conv_in, ssm_in, live, m: ModelConfig,
                one_step: tuple) -> tuple:
    """``mamba2.mixer`` at this block's keys: one ``B``/``C`` row for all
    heads, the gated norm over all of ``d_inner``."""
    return mamba2.mixer(
        lp, x, conv_in, ssm_in, live, one_step, heads=m.mamba_n_heads,
        d_head=m.mamba_d_head, d_state=m.mamba_d_state, d_conv=m.mamba_d_conv,
        groups=m.mamba_n_groups, chunk=m.mamba_chunk_size,
        eps=m.rms_norm_eps, scan=ssm_scan, step=ssm_step,
        norm=rms_norm)


# --------------------------------------------------------------------------- #
# experts
# --------------------------------------------------------------------------- #


def route(logits, k: int) -> tuple:
    """(experts [N, k] int32, weights [N, k] float32): the ``k`` largest
    logits, ties to the lower index, and the softmax over those."""
    top, experts = lax.top_k(logits, k)
    return experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def expert_mlp(lp, x, m: ModelConfig, live) -> tuple:
    """The expert half of a layer on the normed stream ``x`` [B, S, H]:
    (this chip's part of the routed sum + the shared MLP, what
    ``models/experts.py::share`` counted). Rows that are not ``live`` are
    routed nowhere."""
    B, S, H = x.shape
    x2 = x.reshape(B * S, H)
    with jax.named_scope("moe_route"):
        logits = jnp.dot(x2.astype(F32), lp["router"].astype(F32),
                         precision=HIGHEST)
        experts, weights = route(logits, m.num_experts_per_tok)
        w_held = expert_share.held_weights(
            experts, weights, m.ep_rank * m.num_local_experts,
            m.num_local_experts) * live.reshape(B * S, 1).astype(F32)
    y, counted = expert_share.share(lp, x2, w_held)
    return y.reshape(B, S, H), counted


# --------------------------------------------------------------------------- #
# the two kinds of layer
# --------------------------------------------------------------------------- #


def _finish(lp, h, m: ModelConfig, live, out: dict, ssm_stats: tuple):
    """The expert half, and the layer's counters beside its cache leaves."""
    y, moe = expert_mlp(
        lp, rms_norm(h, lp["mlp_norm"], m.rms_norm_eps), m, live)
    h = h + jnp.asarray(m.residual_multiplier, h.dtype) * y
    out[STATS] = jnp.stack(moe + ssm_stats)
    return h, out


def mamba_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                return_kv: bool = False, layer=None, live=None, *,
                first: int = 0, kind_first: int = 0):
    """A Mamba-2 layer, then the experts. ``llama.decoder_layer``'s
    contract; the returned dict also holds ``STATS``. Three shapes of call:
    no cache (a whole sequence from zeros: the state and conv tail behind
    its last live row are returned as a one-slot block), a ``slot`` entry (a
    prefill chunk carries that slot's state on, from zeros where ``pos`` is
    0), neither (a decode step advances every live slot)."""
    m = cfg.model
    live = live_rows(cache, live, h)
    x = rms_norm(h, lp["mixer_norm"], m.rms_norm_eps)
    y, new, decode = carry_state(
        cache, cache, ("conv", "ssm"),
        (((m.mamba_d_conv - 1, conv_width(m)), h.dtype),
         ((m.mamba_n_heads, m.mamba_d_head, m.mamba_d_state), F32)),
        None if cache is None else leaf_row(layer, first, kind_first), pos,
        h, lambda conv_in, ssm_in, step: mamba_mixer(
            lp, x, conv_in, ssm_in, live, m, one_step=step))
    h = h + jnp.asarray(m.residual_multiplier, h.dtype) * y
    if cache is None:
        out = new if return_kv else {}
    else:
        out = {n: v for n, v in cache.items() if n not in ("live", "active")}
        out.update(new)
    return _finish(lp, h, m, live, out, state_counts(live, decode))


def attention_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                    return_kv: bool = False, layer=None, live=None, *,
                    first: int = 0, kind_first: int = 0):
    """A NoPE attention layer (GQA, no rotation, scores times
    ``attention_multiplier``), then the experts. ``cos``/``sin`` are not
    read. K/V go through ``kv_cache.cache_write`` / ``attend`` at this
    layer's row of the ``k``/``v`` leaves."""
    m = cfg.model
    B, S, _ = h.shape
    hd = m.head_dim
    live = live_rows(cache, live, h)
    with jax.named_scope("attn_nope"):
        x = rms_norm(h, lp["mixer_norm"], m.rms_norm_eps)
        q = (x @ lp["wq"]).reshape(B, S, m.num_attention_heads, hd)
        k = (x @ lp["wk"]).reshape(B, S, m.num_key_value_heads, hd)
        v = (x @ lp["wv"]).reshape(B, S, m.num_key_value_heads, hd)
        if cache is None:
            a = kv_cache.decode_attention(
                q, k, v, jnp.full((B,), S, jnp.int32),
                m.attention_multiplier)
            out = {"k": k, "v": v} if return_kv else {}
        else:
            row = leaf_row(layer, first, kind_first)
            out = kv_cache.cache_write(
                {n: c for n, c in cache.items()
                 if n not in ("live", "active")}, k, v, pos, row)
            a = kv_cache.attend(q, out, pos + S, m.attention_multiplier,
                                row, impl=cfg.inference.attend_impl)
        a = a.reshape(B, S, -1) @ lp["wo"]
    h = h + jnp.asarray(m.residual_multiplier, h.dtype) * a
    zero = jnp.zeros((), jnp.int32)
    return _finish(lp, h, m, live, out, (zero, zero, zero))
