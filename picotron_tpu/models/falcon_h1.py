"""The Falcon-H1 block (``model_type: "falcon_h1"``) as pure functions over a
parameter pytree: a PARALLEL hybrid. Every layer runs a Mamba-2 mixer and
GQA attention side by side on one normed input and adds both to the stream,
then a SwiGLU; every projection carries a published muP multiplier. Serving
path only (``Config.validate`` refuses the rest by name).

The equations (``N(.)`` RMSNorm with weight, eps ``rms_norm_eps``; no bias
anywhere but the conv's; every multiplier a scalar of the forward pass, as
written here, never folded into a weight):

- stream: ``h = E[tokens] * embedding_multiplier``; a layer: ``x = N_in(h)``,
  ``h += ssm_out_multiplier * Mamba(x * ssm_in_multiplier) +
  attention_out_multiplier * Attn(x * attention_in_multiplier)``, then ``h +=
  MLP(N_ff(h))``; out: ``logits = (N_f(h) W_head) * lm_head_multiplier``
  (untied);
- ``Attn(u)``: ``q = u W_q``, ``k = (u W_k) * key_multiplier``, ``v = u W_v``
  (GQA); RoPE over the whole head, halves paired, base ``rope_theta``, no
  scaling; causal softmax of ``q k^T / sqrt(head_dim)``; ``W_o``;
- ``Mamba(u)`` (``models/mamba2.py``; ``d_ssm = mamba_n_heads *
  mamba_d_head``, which is NOT ``mamba_expand * hidden_size``; ``G =
  mamba_n_groups``, head ``i`` reads group ``i // (heads / G)``; state
  ``mamba_d_state``): ``[z | x | B | C | dt] = (u W_in) * mup``, ``mup`` the
  vector that holds ``ssm_multipliers[0..4]`` over those five ranges of
  columns (``d_ssm | d_ssm | G N | G N | heads``); the causal conv, the
  recurrence on a float32 state and the skip as the module says them; ``y <-
  N_g(y * silu(z))``, gate first, the mean square over each GROUP's ``d_ssm /
  G`` channels; ``W_out``;
- ``MLP(u) = ((u W_up) * silu((u W_gate) * mlp_multipliers[0])) W_down *
  mlp_multipliers[1]``.

One stacked group: the layers are alike. Every layer keeps a K/V row AND a
state row, so all four cache leaves run over all layers and a layer's row of
each is its index. A prefill chunk carries a slot's state and conv tail on
(``models.carry_state``) and writes its K/V rows (``kv_cache.cache_write``)
in the same layer call; a decode step advances the state leaf in place
(``ops/ssm.py::ssm_step`` on the stacked leaf) and attends through the
stacked flash-decode kernel (``kv_cache.attend``). The state has no token
axis: ``dt = 0`` where a row is not ``live`` freezes it exactly, and the
first chunk of a prompt (``pos == 0``) starts from zeros whatever the slot
held (as ``models/granite_hybrid.py``).

Every layer returns, beside the updated cache leaves, what it counted
(``STATS``, in the order of ``STAT_NAMES``; docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.inference import kv_cache
from picotron_tpu.models import (STATS, carry_state, live_rows, llama, mamba2,
                                 served_whole, state_counts, support)
from picotron_tpu.models.llama import param_bytes  # noqa: F401 - the seam
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.rope import apply_rope
from picotron_tpu.ops.ssm import ssm_scan, ssm_step

# what a layer counts, in the order of the vector (under ``STATS``): the
# recurrent mixers' three under ``granite_hybrid``'s names (live slot-layers a
# decode step advanced, layers decode steps ran, live tokens through a prefill
# scan), then decode steps of a layer's attend and the live keys those
# attends covered (the sum of the live slots' lengths, the fresh row's in)
STAT_NAMES = ("ssm_state_updates", "ssm_layer_steps", "ssm_tokens_scanned",
              "attn_layer_steps", "attn_keys_read")

UNSLICED = ()
# the state has no token axis and cannot be fed a token twice: the engine
# holds the window to whole prefill chunks
CARRIES_STATE = True
LEAVES = ("k", "v", "ssm", "conv")  # the cache's, beside "lengths"
WHY = {  # what the block cannot do yet: no experts, so no share of a layer
    **support.RECURRENT_STATE,
    "training": "no backward through the chunked scan",
    "tp": "the recurrent state has no tp sharding and the block holds no tp "
          "collectives",
}
F32 = jnp.float32


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name (``Config.validate`` calls it)."""
    m = cfg.model
    support.refuse(cfg, for_training, WHY)
    support.positive(m, "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                     "mamba_d_conv", "mamba_n_groups", "mamba_chunk_size",
                     "mamba_d_ssm")
    support.check(
        m,
        (m.mamba_n_heads * m.mamba_d_head != m.mamba_d_ssm,
         f"mamba_n_heads {m.mamba_n_heads} x mamba_d_head {m.mamba_d_head} "
         f"must be mamba_d_ssm {m.mamba_d_ssm}"),
        (m.mamba_n_heads % m.mamba_n_groups or m.mamba_d_ssm
         % m.mamba_n_groups,
         f"mamba_n_groups {m.mamba_n_groups} must divide mamba_n_heads "
         f"{m.mamba_n_heads} and mamba_d_ssm {m.mamba_d_ssm}"),
        (len(m.ssm_multipliers or ()) != 5,
         f"ssm_multipliers {m.ssm_multipliers!r}: one for each of z, x, B, "
         "C, dt"),
        (len(m.mlp_multipliers or ()) != 2,
         f"mlp_multipliers {m.mlp_multipliers!r}: one for the gate, one for "
         "the way down"),
        (m.head_dim % 2, f"head_dim {m.head_dim} must be even (RoPE rotates "
                         "halves)"))
    support.pinned(m, mamba_rms_norm=True, mamba_norm_before_gate=False,
                   mamba_conv_bias=True, mamba_proj_bias=False,
                   rope_scaling=None, tie_word_embeddings=False)


# --------------------------------------------------------------------------- #
# shapes, groups, parameters
# --------------------------------------------------------------------------- #


def conv_width(m: ModelConfig) -> int:
    """Channels the conv runs over: ``x``, ``B`` and ``C`` of every group."""
    return m.mamba_d_ssm + 2 * m.mamba_n_groups * m.mamba_d_state


def mup_vector(m: ModelConfig, dtype):
    """``ssm_multipliers`` laid over the columns of ``in_proj``'s output:
    ``[z d_ssm | x d_ssm | B G N | C G N | dt heads]``."""
    GN = m.mamba_n_groups * m.mamba_d_state
    widths = (m.mamba_d_ssm, m.mamba_d_ssm, GN, GN, m.mamba_n_heads)
    return jnp.concatenate([jnp.full((w,), s, dtype) for w, s
                            in zip(widths, m.ssm_multipliers)])


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    layers)]: one group, the layers are alike."""
    return [("layers", decoder_layer, m.num_hidden_layers)]


def _shapes(m: ModelConfig) -> dict:
    """Matmul leaves of a layer, (in, out) like every weight here."""
    H, I, hd = m.hidden_size, m.intermediate_size, m.head_dim
    return {"wq": (H, m.num_attention_heads * hd),
            "wk": (H, m.num_key_value_heads * hd),
            "wv": (H, m.num_key_value_heads * hd),
            "wo": (m.num_attention_heads * hd, H),
            "in_proj": (H, m.mamba_d_ssm + conv_width(m) + m.mamba_n_heads),
            "out_proj": (m.mamba_d_ssm, H),
            "w_gate": (H, I), "w_up": (H, I), "w_down": (I, H)}


# Seeded weights are drawn so that each mechanism of the block is loud
# enough in the logits for a comparison to see a fault in it (as
# ``granite_hybrid.INIT_GAIN``; PERF.md section 6, PR 65, has the controls'
# readings). First, every matrix a published multiplier stands beside is
# drawn wider by that multiplier's inverse (``_undone``): trained weights
# have grown against their multipliers, and a flat draw under them is a
# model whose keys are a ninetieth (a softmax that is a mean), whose two
# mixers add a hundredth of the stream and whose MLP adds nothing. Then, on
# that unit-scale model: ``wq`` and ``wk`` wider, so that the softmax over
# 1,500 keys has a few large terms; ``wo`` wider (what is left of a mean);
# ``out_proj`` wider (one Mamba head in thirty remembers past a chunk's 512
# rows, and a state dropped there has to be heard through the others);
# ``in_proj``'s B and C columns ``BC_GAIN`` times wider (with the flat draw
# the state's read-out is a fiftieth of the skip ``D x`` beside it). And
# ``SELF_KEY``: a K/V head's keys lean on the queries of its group's first
# head (``W_k += SELF_KEY * W_q[that head]``), so that a row's OWN key
# scores high with that head, as a trained model's newest keys do: with
# independent ``W_q`` and ``W_k`` a row's own key is one of 1,536 alike, and
# an attend that stops one key short reads as sound.
INIT_GAIN = {"wq": 2.0, "wk": 2.0, "wo": 2.0, "out_proj": 3.0}
BC_GAIN = 8.0
SELF_KEY = 0.6


def _undone(m: ModelConfig) -> dict:
    """The draw's width for each matrix a scalar multiplier stands beside:
    the multiplier's inverse (``in_proj``'s is a vector over its columns:
    ``init_params``)."""
    gate, down = m.mlp_multipliers
    return {"wq": 1.0 / m.attention_in_multiplier,
            "wk": 1.0 / (m.attention_in_multiplier * m.key_multiplier),
            "wv": 1.0 / m.attention_in_multiplier,
            "wo": 1.0 / m.attention_out_multiplier,
            "out_proj": 1.0 / m.ssm_out_multiplier,
            "w_gate": 1.0 / gate, "w_down": 1.0 / down}


def _row_blocks(rows: int) -> int:
    """In how many blocks of rows the embedding and the head are drawn: a
    table of gigabytes drawn whole holds its random bits beside it."""
    return max(d for d in range(1, 65) if rows % d == 0)


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key``: linear weights U(+-gain *
    sqrt(1 / fan_in)), the gain ``INIT_GAIN``'s times ``_undone``'s, drawn
    in the model's dtype (``in_proj`` at gain 1, then its columns over
    ``ssm_in_multiplier`` and ``mup_vector`` and its B and C columns
    ``BC_GAIN`` wider; ``wk`` leaning on ``wq`` by ``SELF_KEY``); norm
    weights ones; the Mamba-2 parameters as
    ``mamba2.draw`` says; the embedding N(0, 1) / ``embedding_multiplier``
    (a stream of unit mean square), the untied head U(+-sqrt(1 / hidden)) /
    ``lm_head_multiplier``."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("falcon_h1 is served on one stage (pp_size 1)")
    dt = jnp.dtype(m.dtype)
    H, V, n = m.hidden_size, m.vocab_size, m.num_hidden_layers
    W, Di = conv_width(m), m.mamba_d_ssm
    undone = _undone(m)

    def uniform(k, shape, fan_in, gain=1.0):
        bound = gain * math.sqrt(1.0 / fan_in)
        return jax.random.uniform(k, shape, dt, -bound, bound)

    gkey = jax.random.fold_in(key, 2)
    shapes = sorted(_shapes(m).items())
    layers = {"input_norm": jnp.ones((n, H), dt),
              "mlp_norm": jnp.ones((n, H), dt),
              "gate_norm": jnp.ones((n, Di), dt)}
    for i, (name, shape) in enumerate(shapes):
        layers[name] = uniform(
            jax.random.fold_in(gkey, i), (n,) + shape, shape[-2],
            INIT_GAIN.get(name, 1.0) * undone.get(name, 1.0))
    # the own key: of each K/V head's group of query heads, the first
    g = m.num_attention_heads // m.num_key_value_heads
    first = layers["wq"].reshape(n, H, m.num_key_value_heads, g,
                                 m.head_dim)[:, :, :, 0]
    layers["wk"] = layers["wk"] + (
        first.reshape(n, H, -1) * (SELF_KEY * undone["wk"] / undone["wq"])
    ).astype(dt)
    layers["in_proj"] = mamba2.louder_bc(
        layers["in_proj"] / (m.ssm_in_multiplier * mup_vector(m, dt)),
        Di, W, BC_GAIN)
    layers.update(mamba2.draw(
        [jax.random.fold_in(gkey, len(shapes) + j) for j in range(4)], n,
        heads=m.mamba_n_heads, width=W, d_conv=m.mamba_d_conv, dtype=dt))
    vb, hb = _row_blocks(V), _row_blocks(H)
    return {
        "embed": jax.lax.map(
            lambda k: (jax.random.normal(k, (V // vb, H), F32)
                       / m.embedding_multiplier).astype(dt),
            jax.random.split(jax.random.fold_in(key, 0), vb)).reshape(V, H),
        "final_norm": jnp.ones((H,), dt),
        "lm_head": jax.lax.map(
            lambda k: uniform(k, (H // hb, V), H, 1.0 / m.lm_head_multiplier),
            jax.random.split(jax.random.fold_in(key, 1), hb)).reshape(H, V),
        "layers": layers,
    }


param_pspecs, num_params, cache_pspecs = served_whole(
    "falcon_h1", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# into and out of the stream; serving state
# --------------------------------------------------------------------------- #


def embed_lookup(w, tokens, cfg: Config):
    """``E[tokens] * embedding_multiplier``, in the embedding's dtype."""
    return llama.embed_lookup(w, tokens) * jnp.asarray(
        cfg.model.embedding_multiplier, w.dtype)


def head_logits(params, h, cfg: Config):
    """Final norm, then the untied head, times ``lm_head_multiplier``."""
    with jax.named_scope("falcon_h1/head"):
        logits = llama.head_logits(params, h, cfg)
        return logits * jnp.asarray(cfg.model.lm_head_multiplier,
                                    logits.dtype)


serving_rope_tables = llama.serving_rope_tables  # the plain table


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1) -> dict:
    """Zeroed cache for ``slots`` sequences, every leaf over ALL layers:
    the dense block's ``k``/``v`` [layers, slots, T, kv heads, head_dim];
    ``ssm`` [layers, slots, heads, d_head, d_state] float32; ``conv``
    [layers, slots, d_conv - 1, conv width], the last inputs of the conv."""
    assert not quantized and tp == 1
    cache = kv_cache.init_cache(m, slots, max_seq_len, dtype=dtype)
    n = m.num_hidden_layers
    cache["ssm"] = jnp.zeros((n, slots, m.mamba_n_heads, m.mamba_d_head,
                              m.mamba_d_state), F32)
    cache["conv"] = jnp.zeros((n, slots, m.mamba_d_conv - 1, conv_width(m)),
                              cache["k"].dtype)
    return cache


# --------------------------------------------------------------------------- #
# the two mixers, the MLP, the layer
# --------------------------------------------------------------------------- #


def mamba_mixer(lp, x, conv_in, ssm_in, live, m: ModelConfig,
                one_step: tuple) -> tuple:
    """``mamba2.mixer`` at this block's keys on the normed stream ``x``:
    ``ssm_in_multiplier`` on the way in, ``ssm_multipliers`` over
    ``in_proj``'s columns, ``B`` and ``C`` a group of heads, the gated
    norm's mean square over each group's channels. Without
    ``ssm_out_multiplier``: the layer applies it."""
    return mamba2.mixer(
        lp, x * jnp.asarray(m.ssm_in_multiplier, x.dtype), conv_in, ssm_in,
        live, one_step, heads=m.mamba_n_heads, d_head=m.mamba_d_head,
        d_state=m.mamba_d_state, d_conv=m.mamba_d_conv,
        groups=m.mamba_n_groups, chunk=m.mamba_chunk_size,
        eps=m.rms_norm_eps, scan=ssm_scan,
        step=ssm_step, norm=rms_norm, col_mult=mup_vector(m, x.dtype))


def attention(lp, x, cos, sin, m: ModelConfig, cache, pos, layer, impl: str,
              return_kv: bool) -> tuple:
    """GQA on the normed stream ``x`` [B, S, H]: (output [B, S, H] without
    ``attention_out_multiplier``, the cache leaves with this layer's rows
    written, or without a cache the rows a one-shot prefill would write)."""
    B, S, _ = x.shape
    hd = m.head_dim
    with jax.named_scope("attend"):
        u = x * jnp.asarray(m.attention_in_multiplier, x.dtype)
        q = (u @ lp["wq"]).reshape(B, S, m.num_attention_heads, hd)
        k = (u @ lp["wk"]) * jnp.asarray(m.key_multiplier, x.dtype)
        k = k.reshape(B, S, m.num_key_value_heads, hd)
        v = (u @ lp["wv"]).reshape(B, S, m.num_key_value_heads, hd)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        scale = hd ** -0.5
        if cache is None:
            a = kv_cache.decode_attention(
                q, k, v, jnp.full((B,), S, jnp.int32), scale)
            out = {"k": k, "v": v} if return_kv else {}
        else:
            out = kv_cache.cache_write(cache, k, v, pos, layer)
            a = kv_cache.attend(q, out, pos + S, scale, layer, impl=impl)
        return a.reshape(B, S, -1) @ lp["wo"], out


def mlp(lp, x, m: ModelConfig):
    """The SwiGLU with its two multipliers, on the normed stream."""
    gate_mult, down_mult = (jnp.asarray(s, x.dtype)
                            for s in m.mlp_multipliers)
    with jax.named_scope("mlp"):
        y = (x @ lp["w_up"]) * jax.nn.silu((x @ lp["w_gate"]) * gate_mult)
        return (y @ lp["w_down"]) * down_mult


def decoder_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                  return_kv: bool = False, layer=None, live=None):
    """A layer: both mixers on ``N_in(h)``, then the MLP.
    ``llama.decoder_layer``'s contract, ``layer`` the row of every cache
    leaf; the returned dict also holds ``STATS``. Three shapes of call: no
    cache (a whole sequence from zeros: the K/V rows and the state and conv
    tail behind its last live row are returned), a ``slot`` entry (a prefill
    chunk carries that slot's state on, from zeros where ``pos`` is 0, and
    writes its K/V rows), neither (a decode step advances every live slot's
    state and attends over its keys)."""
    m = cfg.model
    dt = h.dtype
    live = live_rows(cache, live, h)
    leaves = None if cache is None else {
        n: v for n, v in cache.items() if n not in ("live", "active")}
    with jax.named_scope("falcon_h1"):
        x = rms_norm(h, lp["input_norm"], m.rms_norm_eps)
        s, new, decode = carry_state(
            cache, leaves, ("conv", "ssm"),
            (((m.mamba_d_conv - 1, conv_width(m)), dt),
             ((m.mamba_n_heads, m.mamba_d_head, m.mamba_d_state), F32)),
            layer, pos, h, lambda conv_in, ssm_in, step: mamba_mixer(
                lp, x, conv_in, ssm_in, live, m, one_step=step))
        a, out = attention(lp, x, cos, sin, m, leaves, pos, layer,
                           cfg.inference.attend_impl, return_kv)
        if cache is not None or return_kv:
            out.update(new)
        h = h + jnp.asarray(m.ssm_out_multiplier, dt) * s \
            + jnp.asarray(m.attention_out_multiplier, dt) * a
        h = h + mlp(lp, rms_norm(h, lp["mlp_norm"], m.rms_norm_eps), m)
    zero = jnp.zeros((), jnp.int32)
    # a decode step's attend covers each live slot's keys, its fresh one in
    attends = (zero + 1, jnp.sum(jnp.where(live[:, 0], pos + 1, 0),
                                 dtype=jnp.int32)) if decode else (zero, zero)
    out[STATS] = jnp.stack(state_counts(live, decode) + attends)
    return h, out
