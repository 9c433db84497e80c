"""The SDAR-MoE block (``model_type: "sdar_moe"``, SDAR-30B-A3B-Chat) as pure
functions over a parameter pytree: a Qwen3-MoE-shaped layer (GQA with q/k
norm a head and plain RoPE, softmax-routed experts with no shared one in
every layer) that generates by diffusion over blocks. Serving path only
(``Config.validate`` refuses the rest by name).

The equations (``x`` the normed stream; ``N`` RMSNorm with weight, eps
``rms_norm_eps``; no bias in any projection):

- stream: ``h = E[tokens]``; a layer: ``h += Attn(N1(h))``, then ``h +=
  MoE(N2(h))``; out: ``logits = Nf(h) W_head``, untied. ``logits[i]`` scores
  the token AT position ``i`` (no shift: a position not yet decided is fed as
  ``mask_token_id`` and its own logits say what it is);
- attention: ``q = x W_q`` (``num_attention_heads`` of ``head_dim``), ``k = x
  W_k``, ``v = x W_v`` (``num_key_value_heads`` of ``head_dim``); ``q``, ``k``
  RMS-normed a head (one weight vector for queries, one for keys), then RoPE
  on the whole head, halves paired, base ``rope_theta``; scores ``q . k /
  sqrt(head_dim)`` over the visible keys, softmax in float32;
- THE VISIBILITY RULE: key ``j`` is visible to query ``i`` iff ``j //
  block_length <= i // block_length``, positions counted from the sequence's
  start: bidirectional inside a block of ``block_length`` positions, causal
  between blocks (``kv_cache.attend``'s ``block``);
- experts: ``s = softmax(x W_r)`` in float32 over the router's whole width
  (``num_experts * ep_size``); the ``num_experts_per_tok`` largest (ties to
  the lower index), weights ``s[chosen] / sum`` (``norm_topk_prob``); ``y =
  sum_e w_e SwiGLU_e(x)`` over the experts held here (``ep_rank *
  num_experts`` onward); what the absent experts would add is left out.

How it generates is the engine's (``GENERATES``: a round of
``InferenceEngine`` denoises and commits whole blocks, ``engine._blocks_impl``;
the schedule is the configuration's ``block_length``, ``denoising_steps``,
``remasking``, ``confidence_threshold``, ``mask_token_id``); the layer only
holds the rule above, in three shapes of call: no cache (a prompt's whole
blocks from position 0), a ``slot`` entry (a prefill chunk of whole blocks of
that slot), neither (every slot's current block, ``block_length`` rows
written at its length and then attended: a denoise forward's rows are
provisional, beyond the length for every later reader, and the next forward
overwrites them).

The cache holds the dense block's two leaves, ``k`` and ``v`` [layers, slots,
max_seq_len, kv heads, head_dim] as ``kv_cache.init_cache`` lays them, and
not Keye's one ``kv`` row: nothing gathers scattered rows here, and with
``k``/``v`` a layer goes through ``kv_cache.cache_write`` / ``attend`` and
the flash-decode kernels as the Llama block's does. Every layer returns,
beside the updated leaves, what it counted (``STATS``, in the order of
``STAT_NAMES``: the expert share's; docs/OBSERVABILITY.md)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from picotron_tpu.config import REMASKING, Config, ModelConfig
from picotron_tpu.inference import kv_cache
from picotron_tpu.models import (STATS, live_rows, llama, normed_gqa_moe,
                                 served_whole, support)
from picotron_tpu.models import experts as expert_share
from picotron_tpu.models.llama import param_bytes  # noqa: F401 - the seam
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.rope import apply_rope

# how the engine generates with this block: whole blocks of
# ``model.block_length`` positions a round, not a token a step
GENERATES = "blocks"
BLOCK_LENGTHS = (1, 2, 4, 8, 16, 32)
STAT_NAMES = expert_share.STAT_NAMES  # what a layer counts
UNSLICED = expert_share.UNSLICED
LEAVES = ("k", "v")  # the cache's, beside "lengths"

# Seeded weights, Keye's gains as they stood (``keye_vl2.INIT_GAIN``, so that
# the two cells draw one tree at the shared leaves): ``wo`` drawn wider so
# that the attention, and with it which keys a row saw, is heard in a
# comparison of logits; the routed experts' ``w2`` half as wide, because the
# sound program's bfloat16 stream breaks a near-tie of the router otherwise
# than the float32 reference now and then, and a whole expert chosen
# otherwise should not be the comparison's largest term.
INIT_GAIN = {"wo": 4.0, "w2": 0.5}

# what the block cannot do yet, and why (``support.refuse``)
WHY = {
    **support.LLAMA_ONLY,
    "training": "no backward through the expert share, and the two-copy "
                "noisy/clean mask a diffusion loss trains under is not built",
    "tp": "the block holds no tp collectives; its share of a layer is "
          "ep_size/ep_rank",
    "dp": "a round of blocks has no slot axis over 'dp'",
    "paged": "the paged attends see a causal band, and a block's provisional "
             "rows would be written into shared pages; set kv_layout: "
             "'contiguous'",
    "kv_int8": "K and V are stored in the model's dtype",
    "speculation": "a round already feeds a block of rows a slot; a verify "
                   "scores the token behind each fed one, which this model's "
                   "logits do not",
    "flash": "the sliced kernel a forced 'flash' runs under a prefill chunk "
             "sees a causal band, not the block-causal one ('auto' runs the "
             "stacked kernel for a block's forward and the dense rule for "
             "a chunk)",
    "overlap": "a round's first block is fed from the host (the prompt's "
               "remainder), and the lookahead dispatch has no such operand",
}


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name (``Config.validate`` calls it)."""
    m, inf = cfg.model, cfg.inference
    support.refuse(cfg, for_training, WHY)
    Bd, T = m.block_length, m.denoising_steps
    support.check(
        m,
        (Bd not in BLOCK_LENGTHS,
         f"block_length {Bd} is not one of {BLOCK_LENGTHS}"),
        (not 1 <= T <= max(Bd, 1),
         f"denoising_steps {T} outside [1, block_length {Bd}]"),
        (m.remasking not in REMASKING,
         f"remasking {m.remasking!r} is not one of {REMASKING}"),
        (not 0 <= m.mask_token_id < m.vocab_size,
         f"mask_token_id {m.mask_token_id} outside the vocabulary of "
         f"{m.vocab_size}"),
        (inf.prefill_chunk % Bd or inf.decode_block_len % Bd,
         f"inference.prefill_chunk {inf.prefill_chunk} and decode_block_len "
         f"{inf.decode_block_len} must be multiples of block_length {Bd}: a "
         "chunk and a round hold whole blocks"),
        (m.head_dim % 2, f"head_dim {m.head_dim} must be even (RoPE rotates "
                         "halves)"),
        (m.rope_scaling is not None,
         f"rope_scaling {m.rope_scaling!r}: the block rotates by the plain "
         "table (published: null)"))
    normed_gqa_moe.validate_experts(cfg)


# --------------------------------------------------------------------------- #
# shapes, groups, parameters
# --------------------------------------------------------------------------- #


router_width = normed_gqa_moe.router_width


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    layers)]: one group, the layers are alike."""
    return [("layers", decoder_layer, m.num_hidden_layers)]


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key`` (``normed_gqa_moe.draw_tree``:
    linear weights U(+-gain * sqrt(1 / fan_in)), ``INIT_GAIN`` else 1, in the
    model's dtype; norm weights ones; the embedding N(0, 1))."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("sdar_moe is served on one stage (pp_size 1)")
    H, hd = m.hidden_size, m.head_dim
    return normed_gqa_moe.draw_tree(
        key, m, {**normed_gqa_moe.attention_shapes(m),
                 **normed_gqa_moe.expert_shapes(m)},
        {"attn_norm": H, "mlp_norm": H, "q_norm": hd, "k_norm": hd},
        INIT_GAIN)


param_pspecs, num_params, cache_pspecs = served_whole(
    "sdar_moe", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# into and out of the stream; serving state
# --------------------------------------------------------------------------- #


embed_lookup = llama.embed_lookup  # no multiplier
head_logits = llama.head_logits  # final norm, then the untied head
serving_rope_tables = llama.serving_rope_tables  # the plain table


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1) -> dict:
    """Zeroed cache for ``slots`` sequences: the dense block's ``k`` and
    ``v``."""
    assert not quantized and tp == 1
    return kv_cache.init_cache(m, slots, max_seq_len, dtype=dtype)


# --------------------------------------------------------------------------- #
# the layer
# --------------------------------------------------------------------------- #


def router_scores(logits):
    """The router's scores [N, width] float32 of its logits: a softmax over
    the whole width."""
    return jax.nn.softmax(logits, axis=-1)


def expert_mlp(lp, x, m: ModelConfig, live) -> tuple:
    """The expert half of a layer (``normed_gqa_moe.expert_mlp``)."""
    return normed_gqa_moe.expert_mlp(lp, x, m, live, router_scores,
                                     "sdar/router")


def attention(lp, x, cos, sin, m: ModelConfig, cache, pos, layer,
              impl: str, return_kv: bool) -> tuple:
    """The attention half of a layer on the normed stream ``x`` [B, S, H]
    under the block-causal rule: (output [B, S, H], the cache leaves with
    this layer's rows written, or without a cache the rows a one-shot
    prefill would write). The fresh rows begin on a block boundary (``pos``
    [B], or 0)."""
    B, S, _ = x.shape
    q, k, v = normed_gqa_moe.qkv(lp, x, m, rms_norm)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    scale, Bd = m.head_dim ** -0.5, m.block_length
    if cache is None:
        with jax.named_scope("sdar/attend_chunk"):
            a = kv_cache.decode_attention(
                q, k, v, jnp.full((B,), S, jnp.int32), scale, Bd)
        out = {"k": k, "v": v} if return_kv else {}
    else:
        # one slot's chunk of whole blocks, or every slot's current block
        with jax.named_scope("sdar/attend_chunk" if "slot" in cache
                             else "sdar/attend_block"):
            # four heads a token are half a register tile: left free, a
            # chunk's contractions re-lay both leaves whole with the tokens
            # along the lanes (two copies of 4.5 GB, and the chunk does not
            # fit); ``cache_write`` holds every leaf it writes row-major
            out = kv_cache.cache_write(cache, k, v, pos, layer)
            a = kv_cache.attend(q, out, pos + S, scale, layer, impl=impl,
                                block=Bd)
    return a.reshape(B, S, -1) @ lp["wo"], out


def decoder_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                  return_kv: bool = False, layer=None, live=None):
    """A layer: the block-causal attention, then the routed experts held
    here, each on the normed stream. ``llama.decoder_layer``'s contract; the
    returned dict also holds ``STATS``."""
    m = cfg.model
    live = live_rows(cache, live, h)
    attn_cache = None if cache is None else {
        n: v for n, v in cache.items() if n not in ("live", "active")}
    a, out = attention(
        lp, rms_norm(h, lp["attn_norm"], m.rms_norm_eps), cos, sin, m,
        attn_cache, pos, layer, cfg.inference.attend_impl, return_kv)
    h = h + a
    y, moe = expert_mlp(lp, rms_norm(h, lp["mlp_norm"], m.rms_norm_eps), m,
                        live)
    out[STATS] = jnp.stack(moe)
    return h + y, out
