"""Experiment configuration.

One JSON file per experiment, schema mirroring the reference's
``template/base_config.json:1-52`` (sections: distributed / model / training /
dataset / checkpoint / logging). The reference's second, implicit config
channel — environment variables like FLASH_ATTEN / CONTEXT_PARALLEL / DTYPE
(reference train.py:65-68, model.py:147) — is deliberately replaced by explicit
fields here (``model.attention_impl``, ``model.dtype``); SURVEY.md §5.6 calls
that channel an implementation wart, not a capability.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Optional


def parse_rank_at_step(name: str, spec: str) -> tuple[int, int]:
    """Parse a ``"RANK:STEP"`` pod-chaos spec (resilience
    ``chaos_*_rank_at_step`` fields) into ``(rank, step)``; "" (off) ->
    ``(-1, 0)``. Lives here rather than resilience/chaos.py so validate()
    stays importable without pulling in jax."""
    if not spec:
        return -1, 0
    rank_s, sep, step_s = spec.partition(":")
    try:
        if not sep:
            raise ValueError
        rank, step = int(rank_s), int(step_s)
        if rank < 0 or step < 1:
            raise ValueError
    except ValueError:
        raise ValueError(
            f'{name} must be "RANK:STEP" with RANK >= 0 and STEP >= 1 '
            f'(got {spec!r})') from None
    return rank, step


@dataclass
class DistributedConfig:
    """4D topology sizes. Grid ordering is (dp, pp, cp, tp), tp fastest-varying,
    mirroring the reference rank grid (process_group_manager.py:13) so that tp
    neighbors sit on the innermost ICI dimension and dp on the outermost."""

    tp_size: int = 1
    cp_size: int = 1
    pp_size: int = 1
    dp_size: int = 1
    pp_engine: str = "1f1b"  # "afab" | "1f1b"   (reference train.py:223-229)
    # Interleaved 1F1B (virtual pipeline stages, beyond the reference —
    # SURVEY §2.3 notes "no interleaved/virtual stages"): each device holds
    # pp_interleave non-contiguous model chunks and the schedule cycles
    # through them, shrinking the pipeline bubble by the interleave factor.
    # Requires pp_engine="1f1b", num_hidden_layers % (pp*v) == 0, and
    # gradient_accumulation_steps % pp == 0.
    pp_interleave: int = 1
    use_cpu: bool = False  # run on host CPU devices (reference gloo path, train.py:83)
    # Zigzag context-parallel layout: each cp rank owns sequence chunks
    # (r, 2n-1-r), balancing causal ring-attention work across ranks. False =
    # contiguous chunks, faithful to the reference (its zigzag TODO:
    # tests/test_dataloader.py:136).
    cp_zigzag: bool = False
    # Context-parallel algorithm: "ring" = ppermute K/V ring attention (the
    # reference's mode); "ulysses" = DeepSpeed-style all-to-all sequence
    # parallelism (beyond the reference, SURVEY §2.3): one all-to-all swaps
    # seq-sharding for head-sharding, a single full-sequence (flash)
    # attention runs per rank, one all-to-all swaps back. Needs local heads
    # (num_attention_heads / tp) divisible by cp; incompatible with
    # cp_zigzag (it is load-balanced by construction).
    cp_impl: str = "ring"
    # Megatron-style sequence parallelism: between TP blocks the activation
    # sequence axis is sharded over 'tp' (all-gather entering column-parallel
    # matmuls, reduce-scatter leaving row-parallel ones). Same wire bytes as
    # plain TP, residual stream / norms / saved boundaries shrink by 1/tp.
    # The reference only TODOs this (utils.py:66); beyond-parity feature.
    tp_sequence_parallel: bool = False
    # ZeRO stage 1: shard optimizer state (and the update compute) over 'dp'.
    # Gradients reduce-scatter over dp instead of all-reducing, each rank
    # updates its 1/dp chunk of the (flattened) params, updated params
    # all-gather back. Cuts AdamW state memory by dp at identical numerics.
    # Out of the reference's scope (SURVEY.md §2.3 ZeRO row); beyond-parity.
    zero1: bool = False
    # FSDP / ZeRO stage 3 for the decoder-layer stack: layer params rest
    # dp-sharded on their hidden-size axis (models/llama.py:param_pspecs),
    # are all-gathered just in time inside each layer's forward
    # (decoder_layer), and the gather's AD transpose reduce-scatters the
    # grads back — params, grads, and optimizer state for the stack all
    # shrink by dp. Embedding/LM-head/final-norm stay replicated (they are
    # pp-owned and small relative to the stack at depth). Requires
    # hidden_size % dp == 0; mutually exclusive with zero1 (redundant —
    # FSDP already shards the stack's state). Beyond-parity feature.
    fsdp: bool = False
    # Build the training step under shard_map's varying-manual-axes checker
    # (jax check_vma): every replicated-vs-varying typing error — the class
    # of bug the equivalence suite can only catch dynamically — becomes a
    # static trace-time error. DIAGNOSTIC mode, not the production default:
    # the checker auto-inserts pvary casts whose AD transposes are real
    # psums, which resequences reductions (loss trajectories drift at the
    # 1e-4..1e-2 level on zero1/fsdp) and deadlocks inside lax.cond-gated
    # stage branches. Incompatible with pp_engine='afab' (jax's scan
    # transpose does not yet type vma — upstream limitation) and with
    # cond stage gating (collectives inside single-stage branches) — on a
    # CPU-only box set use_cpu=true so the default stage_gating='auto'
    # resolves to where-masking and the checker can run (validate()'s
    # rejection error names the same fix).
    check_vma: bool = False
    # How per-stage embed/loss work is gated to its owning pipeline stage
    # (models/llama.py::_stage_gating): "cond" = lax.cond, the branch only
    # runs on the owning stage (what production TPU pipelines execute);
    # "where" = compute-both masking (collective-rendezvous-safe on the XLA
    # CPU runtime, pre-gating FLOP cost); "auto" = cond on TPU, where on
    # CPU. "cond" on a CPU mesh is supported for configs whose gated
    # branches carry no collectives (tp=1 pipelines) — the equivalence
    # suite uses it so the exact program a TPU pod runs is validated
    # off-chip.
    stage_gating: str = "auto"


# the values of ``ModelConfig.remasking`` (a block that generates by diffusion
# over blocks: ``inference/sampling.py::confidence_unmask``)
REMASKING = ("low_confidence_static", "low_confidence_dynamic")


@dataclass
class ModelConfig:
    name: str = "HuggingFaceTB/SmolLM-1.7B"
    num_hidden_layers: int = 24
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    hidden_size: int = 2048
    intermediate_size: int = 8192
    vocab_size: int = 49152
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 2048
    dtype: str = "bfloat16"  # compute/param dtype (reference train.py:76-77)
    # "auto": pallas flash attention on TPU, XLA sdpa elsewhere.
    # Replaces the reference's FLASH_ATTEN env switch (model.py:147-157).
    attention_impl: str = "auto"  # "auto" | "sdpa" | "flash"
    # Pallas flash-attention tile sizes; None = kernel defaults (512x512,
    # measured optimal on v5e at seq 2048/D64 and 4096/D128 — see
    # ops/pallas/flash_attention.py). Tuning knobs for other chips/shapes.
    flash_block_q: Optional[int] = None
    flash_block_k: Optional[int] = None
    # Kernel data layout: "folded" (default) reshapes [B,S,H,D] ->
    # [B*H,S,D] around a kernel call, a relayout copy of every operand; at
    # heads of 64 the training layer stack takes the default's paired form
    # instead, two heads to a 128-lane row of [B,S,H*D] and no copy
    # (llama.flash_heads_per_row says when; a prefill and context
    # parallelism keep the fold). "merged" is that idea for head_dim % 128
    # == 0. "bshd" squeezes the head out of [B,S,H,D] blocks: interpret
    # mode only, Mosaic refuses it on a chip (ops/pallas/flash_attention.py).
    flash_layout: str = "folded"
    use_pallas_rmsnorm: Optional[bool] = None  # None = auto (TPU only)
    # gather logits over tp before the loss (reference tensor_parallel.py:48-50
    # gather_output=True); False = vocab-parallel cross-entropy (faster).
    # Only consulted by eval-time forward_logits; the training loss path is
    # picked by loss_impl.
    gather_logits: bool = True
    # training loss: "auto" (= fused), "fused" (row-chunked linear+CE, never
    # materializes fp32 logits), "gathered" (reference-parity
    # all-gather + plain CE), "vocab_parallel" (local logits, psum'd stats).
    loss_impl: str = "auto"
    # Which block ``models/`` builds (models.model_module): "llama" (every
    # dense MHA/GQA + SwiGLU model), "deepseek_v32" (latent attention
    # with a learned sparse selection, routed and shared experts —
    # models/deepseek_v32.py, serving path only) or "granitemoehybrid"
    # (Mamba-2 and NoPE attention layers by ``layer_types``, routed and
    # shared experts behind each — models/granite_hybrid.py, serving path
    # only; its fields follow DeepSeek's) or "minicpm_sala" (lightning
    # linear-attention and block-sparse NoPE attention layers by
    # ``mixer_types``, a SwiGLU behind each — models/minicpm_sala.py,
    # serving path only; its fields are at the end) or "afmoe" (gated
    # attention over a window or over everything by ``layer_types``, a
    # SwiGLU or routed and shared experts behind it — models/afmoe.py,
    # serving path only) or "mimo_v2" (full and sliding attention by
    # ``hybrid_layer_pattern`` with K/V heads counted by kind, keys wider
    # than values, a learned sink in the sliding softmax, a SwiGLU or routed
    # experts behind it — models/mimo_v2.py, serving path only) or "KeyeVL2"
    # (Keye-VL-2.0's language model: GQA with q/k norm a head, M-RoPE, a
    # learned top-k selection whose chosen K/V rows a decode step gathers,
    # softmax-routed experts in every layer — models/keye_vl2.py, serving
    # path only) or "nemotron_h" (Nemotron 3's hybrid: a layer is ONE
    # sublayer, Mamba-2 with B and C a group of heads, NoPE attention, or
    # LatentMoE, two-matrix relu^2 experts routed in a latent, by
    # ``hybrid_override_pattern`` — models/nemotron_h.py, serving path only)
    # or "solar_open2" (Solar Open 2: Kimi-delta-attention layers, a gated
    # delta rule with a decay for every key channel, ``gqa_interval`` of
    # them between two gated NoPE GQA layers by ``gqa_layers``, routed and
    # shared experts behind each — models/solar_open2.py, serving path only;
    # its fields are behind Nemotron's) or "sdar_moe" (generation by
    # diffusion over blocks on Qwen3-MoE-shaped layers — models/sdar_moe.py)
    # or "falcon_h1" (Falcon-H1's parallel hybrid: a Mamba-2 mixer and GQA
    # attention side by side in every layer, muP multipliers on every
    # projection — models/falcon_h1.py, serving path only; its fields are
    # the last). The fields below are the
    # published ``config.json`` keys of the DeepSeek block, by their own
    # names, and are read by no other block (but ``num_experts_per_tok``,
    # ``ep_size`` and ``ep_rank``, which both expert blocks read).
    model_type: str = "llama"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # experts HELD here: this chip's share of the layer's
    # n_routed_experts * ep_size, those from ep_rank * n_routed_experts on.
    # The router keeps its whole width and its experts per token; what the
    # absent experts would add is left out (docs/INFERENCE.md).
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    norm_topk_prob: bool = True
    # an int for ``deepseek_v32`` (1: every layer behind the dense ones); a
    # list for ``mimo_v2``, one entry a layer held (0: SwiGLU, 1: experts)
    moe_layer_freq: Any = 1
    first_k_dense_replace: int = 0
    num_nextn_predict_layers: int = 0
    rope_scaling: Optional[dict] = None  # the published YaRN group, whole
    ep_size: int = 1
    ep_rank: int = 0
    # "granitemoehybrid": the published keys of that block. ``layer_types``
    # names each layer's mixer ("mamba" | "attention"), one entry a layer.
    # ``num_local_experts`` counts the experts HELD here, those from
    # ``ep_rank * num_local_experts`` on of a router ``num_local_experts *
    # ep_size`` wide; ``intermediate_size`` is one expert's width.
    layer_types: Optional[list] = None
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_expand: int = 2
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    num_local_experts: int = 0
    shared_intermediate_size: int = 0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 1.0
    logits_scaling: float = 1.0
    position_embedding_type: str = "rope"
    tie_word_embeddings: bool = False
    # "minicpm_sala": the published keys of that block. ``mixer_types``
    # names each layer's mixer ("minicpm4": block-sparse attention |
    # "lightning-attn"), one entry a layer held here. ``sparse_config`` is
    # the sparse layers' group (kernel_size, kernel_stride, block_size,
    # init_blocks, window_size, topk, dense_len). The held layers are
    # ``first_layer`` onward of a model ``total_layers`` deep (0: as deep as
    # what is held): the residual multiplier ``scale_depth /
    # sqrt(total_layers)`` and the decay slopes are the whole model's.
    mixer_types: Optional[list] = None
    lightning_nh: int = 0
    lightning_nkv: int = 0
    lightning_head_dim: int = 0
    lightning_scale: str = "1/sqrt(d)"
    lightning_use_rope: bool = True
    attn_use_rope: bool = True
    attn_use_output_gate: bool = False
    qk_norm: bool = False
    use_output_norm: bool = False
    use_output_gate: bool = False
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    dim_model_base: int = 0
    mup_denominator: int = 0
    sparse_config: Optional[dict] = None
    first_layer: int = 0
    total_layers: int = 0
    # "afmoe" (Trinity): the published keys of that block, beside
    # ``layer_types`` ("sliding_attention" | "full_attention", one entry a
    # layer held), ``num_experts_per_tok``, ``moe_intermediate_size``,
    # ``n_group``/``topk_group`` (1: no group limit), ``ep_size``/``ep_rank``.
    # ``num_experts`` counts the experts HELD here, those from ``ep_rank *
    # num_experts`` on of a router ``num_experts * ep_size`` wide;
    # ``num_dense_layers`` the leading layers held whose MLP is a SwiGLU.
    # The expert layers held are published layers ``first_layer`` onward of
    # a model ``total_layers`` deep (0: unplaced), which
    # ``global_attn_every_n_layers`` holds ``layer_types`` to (a full layer
    # where ``(index + 1) % n == 0``; 0: unchecked). No forward reads them.
    sliding_window: int = 0
    global_attn_every_n_layers: int = 0
    num_dense_layers: int = 0
    num_experts: int = 0
    num_shared_experts: int = 0
    route_norm: bool = True
    route_scale: float = 1.0
    score_func: str = "sigmoid"
    mup_enabled: bool = False
    # "mimo_v2" (MiMo-V2.5's language model): the published keys of that
    # block, beside ``head_dim``/``v_head_dim`` (keys wider than values),
    # ``sliding_window``, ``n_routed_experts`` (the experts HELD here of a
    # router ``n_routed_experts * ep_size`` wide), ``num_experts_per_tok``,
    # ``moe_intermediate_size``, ``moe_layer_freq`` (a list), ``scoring_func``,
    # ``topk_method``, ``norm_topk_prob``, ``n_group``/``topk_group``,
    # ``rope_scaling`` (type "default": none), ``ep_size``/``ep_rank``,
    # ``first_layer``/``total_layers``. ``hybrid_layer_pattern`` names each
    # held layer's attention (0 full, 1 sliding); the ``swa_*`` keys are the
    # sliding layers' heads, widths and RoPE base, ``num_key_value_heads``,
    # ``head_dim``, ``v_head_dim`` and ``rope_theta`` the full layers';
    # ``partial_rotary_factor`` is the share of a head RoPE rotates;
    # ``layernorm_epsilon`` repeats ``rms_norm_eps`` (0: not given).
    hybrid_layer_pattern: Optional[list] = None
    swa_num_attention_heads: int = 0
    swa_num_key_value_heads: int = 0
    swa_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    partial_rotary_factor: float = 1.0
    attention_value_scale: float = 1.0
    add_swa_attention_sink_bias: bool = False
    add_full_attention_sink_bias: bool = False
    layernorm_epsilon: float = 0.0
    # "KeyeVL2" (Keye-VL-2.0's language model): the published keys of that
    # block, beside ``head_dim``, ``num_experts`` (the experts HELD here of a
    # router ``num_experts * ep_size`` wide), ``num_local_experts`` (as
    # published it repeats the router's width: 0, or ``num_experts *
    # ep_size``), ``num_experts_per_tok``, ``moe_intermediate_size``,
    # ``norm_topk_prob``, ``rope_scaling`` (type "default" with
    # ``mrope_section``, the pairs of a head each position stream rotates),
    # ``ep_size``/``ep_rank``, ``first_layer``/``total_layers``.
    # ``sa_config`` is the sparse attention's group (``indexer_num_heads``,
    # ``indexer_head_dim``, ``indexer_num_kv_heads``, ``topk``; its
    # ``q_chunk_size``/``kv_chunk_size`` are read by no forward);
    # ``decoder_sparse_step`` 1 and ``mlp_only_layers`` [] say that every
    # layer's MLP is the routed experts.
    sa_config: Optional[dict] = None
    decoder_sparse_step: int = 1
    mlp_only_layers: Optional[list] = None
    # "nemotron_h" (Nemotron 3 Super): the published keys of that block,
    # beside ``head_dim``, ``n_routed_experts`` (the experts HELD here of a
    # router ``n_routed_experts * ep_size`` wide), ``num_experts_per_tok``,
    # ``moe_intermediate_size``, ``n_shared_experts``, ``norm_topk_prob``,
    # ``routed_scaling_factor``, ``n_group``/``topk_group``,
    # ``mamba_proj_bias``, ``num_nextn_predict_layers`` (0: the
    # multi-token-prediction layer is not held), ``tie_word_embeddings``,
    # ``ep_size``/``ep_rank``. ``hybrid_override_pattern`` is one letter a
    # layer held: ``M`` Mamba-2, ``E`` experts, ``*`` attention; a layer is
    # that one sublayer. ``n_groups`` B/C groups share the ``mamba_num_heads``
    # heads of ``mamba_head_dim``; ``moe_latent_size`` is the width the routed
    # experts work in, ``moe_shared_expert_intermediate_size`` the shared
    # expert's on the stream; the norms' eps is ``rms_norm_eps`` (the
    # published ``layer_norm_epsilon``).
    hybrid_override_pattern: str = ""
    mamba_num_heads: int = 0
    mamba_head_dim: int = 0
    ssm_state_size: int = 0
    conv_kernel: int = 4
    n_groups: int = 1
    chunk_size: int = 128
    use_conv_bias: bool = True
    mlp_hidden_act: str = "silu"
    moe_latent_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    # "solar_open2" (Solar Open 2): the published keys of that block, beside
    # ``head_dim``, ``n_routed_experts`` (the experts HELD here of a router
    # ``n_routed_experts * ep_size`` wide), ``num_experts_per_tok``,
    # ``moe_intermediate_size``, ``n_shared_experts``, ``norm_topk_prob``,
    # ``routed_scaling_factor``, ``first_k_dense_replace`` (0: every layer
    # has experts), ``tie_word_embeddings``, ``ep_size``/``ep_rank``.
    # ``gqa_layers`` lists the held layers whose mixer is the gated NoPE GQA
    # (``gqa_interval`` Kimi-delta-attention layers between two of them; 0:
    # unchecked); ``linear_attn_config`` is the KDA layers' group
    # (``num_heads``, ``head_dim`` for keys and values alike,
    # ``short_conv_kernel_size``, ``num_kv_heads`` null: as many as heads);
    # ``use_rope`` false: the GQA layers rotate nothing; ``use_gqa_gate``: a
    # sigmoid gate on their output; ``kda_use_full_proj``: the decay's and
    # the gate's projections as one matrix each, not through a rank of
    # ``head_dim``; ``kda_allow_neg_eigval``: the write strength doubled,
    # into (0, 2).
    linear_attn_config: Optional[dict] = None
    gqa_layers: Optional[list] = None
    gqa_interval: int = 0
    use_gqa_gate: bool = False
    use_rope: bool = True
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = False
    # "sdar_moe" (SDAR-30B-A3B-Chat): the published keys of that block are
    # "KeyeVL2"'s less ``sa_config`` and ``rope_scaling`` (``num_experts``
    # HELD of a router ``num_experts * ep_size`` wide, ``num_experts_per_tok``,
    # ``moe_intermediate_size``, ``norm_topk_prob``, ``decoder_sparse_step``,
    # ``mlp_only_layers``, ``ep_size``/``ep_rank``, ``first_layer``/
    # ``total_layers``). It generates by diffusion over blocks, and the
    # schedule is the configuration's: blocks of ``block_length`` positions
    # (bidirectional inside, causal between), up to ``denoising_steps``
    # forwards a block, ``remasking`` (one of ``REMASKING``) at
    # ``confidence_threshold``
    # (``inference/sampling.py::confidence_unmask``), ``mask_token_id`` the
    # id a position not yet decided is fed as.
    block_length: int = 0
    denoising_steps: int = 0
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 0
    # "falcon_h1" (Falcon-H1): a parallel hybrid, a Mamba-2 mixer and GQA
    # attention side by side on one normed input in every layer and a SwiGLU
    # behind them. The published keys of that block, beside ``head_dim``,
    # ``embedding_multiplier``, ``rope_scaling``, ``tie_word_embeddings`` and
    # the ``mamba_*`` above (``mamba_expand`` is read by no forward of it:
    # the mixer's width is ``mamba_d_ssm`` = ``mamba_n_heads`` x
    # ``mamba_d_head``, given, and not a multiple of ``hidden_size``).
    # ``mamba_rms_norm``: the gated norm's mean square over each of
    # ``mamba_n_groups`` groups' channels; ``mamba_norm_before_gate`` false:
    # gate first. The multipliers are scalars of the forward pass, never
    # folded into a weight: ``key_multiplier`` on ``k``,
    # ``attention_in/out_multiplier`` and ``ssm_in/out_multiplier`` on either
    # side of the two mixers, ``ssm_multipliers`` (z, x, B, C, dt) on the
    # columns of ``in_proj``'s output, ``mlp_multipliers`` (gate, down),
    # ``lm_head_multiplier`` on the logits.
    mamba_d_ssm: int = 0
    mamba_rms_norm: bool = True
    mamba_norm_before_gate: bool = False
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Optional[list] = None
    mlp_multipliers: Optional[list] = None
    lm_head_multiplier: float = 1.0
    # the published ``head_dim`` where it is not hidden_size / heads (afmoe:
    # 128 of 3072 / 48); 0: derived, and it follows ``hidden_size``
    head_dim: int = 0


def _get_head_dim(self) -> int:
    return self._head_dim or self.hidden_size // self.num_attention_heads


def _set_head_dim(self, value) -> None:
    self._head_dim = int(value or 0)


# a field for ``__init__`` and ``dataclasses.fields`` (a configuration may
# list it), a property to every reader: derived unless it was given
ModelConfig.head_dim = property(_get_head_dim, _set_head_dim)


@dataclass
class TrainingConfig:
    seed: int = 42
    learning_rate: float = 3e-4
    # LR schedule (beyond the reference, which trains at constant lr,
    # train.py:209): "constant" | "cosine" | "linear", with optional linear
    # warmup from 0 over lr_warmup_steps. Decay runs to
    # learning_rate * lr_min_ratio over lr_decay_steps (default:
    # total_train_steps). The default (constant, no warmup) keeps the
    # optimizer state structurally identical to a plain float lr.
    lr_schedule: str = "constant"
    lr_warmup_steps: int = 0
    lr_min_ratio: float = 0.0
    lr_decay_steps: Optional[int] = None
    # torch AdamW defaults — the reference passes only lr (train.py:209)
    weight_decay: float = 0.01
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    grad_clip: float = 0.0  # 0 = off
    total_train_steps: int = 100
    seq_length: int = 1024
    micro_batch_size: int = 1
    gradient_accumulation_steps: int = 1
    max_tokens: Optional[int] = None
    # Train on only the first N raw dataset examples (reference
    # data.py:34-35, template/base_config.json:27: select(range(min(N,
    # len)))) — applied before tokenization on the HF path; the synthetic
    # stream has no documents, so there the cap applies to packed samples.
    num_samples: Optional[int] = None
    # Optimizer steps fused into one device dispatch (lax.scan over stacked
    # batches). >1 removes per-step host latency; losses are still reported
    # per step. Checkpoint/log boundaries snap to multiples of this.
    steps_per_call: int = 1
    # "full": remat every decoder layer (jax.checkpoint); "none": store all;
    # "save_attn": remat layers but keep flash-attention out+LSE (the
    # backward never re-runs the attention forward kernel).
    # Applies to the AD engines (afab, pp=1); the 1f1b engine checkpoints at
    # layer boundaries by construction — equivalent to "full" — and ignores
    # this knob (models/llama.py::stage_fwd_save, docs/PP_COST.md).
    remat: str = "full"
    # dtype gradients accumulate in across microbatches: "float32" (the
    # reference's main_grad policy, data_parallel.py:66,81) or "param"
    # (param dtype; halves grad memory, useful single-chip). Only consulted
    # when pp_size == 1 — both pipeline engines always accumulate fp32
    # (validate() rejects "param" with pp_size > 1).
    grad_accum_dtype: str = "float32"


@dataclass
class DatasetConfig:
    name: str = "synthetic"  # "synthetic" or an HF dataset path
    split: str = "train"
    text_column: str = "text"
    num_workers: int = 0
    num_proc: int = 1
    subset_name: Optional[str] = None
    # Packed corpora at or under this many tokens materialize as one host
    # numpy array (fastest gathers); anything larger stays in the datasets
    # arrow cache (disk-mapped, RAM stays bounded by the batch) — the
    # reference keeps its grouped dataset arrow-backed the same way
    # (picotron/data.py:57-100). Default 50M tokens = 200 MB of int32.
    max_in_memory_tokens: int = 50_000_000


@dataclass
class CheckpointConfig:
    save_dir: str = "checkpoints"
    save_frequency: int = 0  # 0 = disabled
    load_path: str = ""  # orbax checkpoint dir to resume from
    # HF-format safetensors file/dir to initialize weights from before training
    # (the reference's bootstrap path, checkpoint.py:50-102)
    hf_bootstrap_path: str = ""
    # Reference semantics: the reference loads the HF file, then deliberately
    # re-randomizes — the files act as shape/name templates for pre-training
    # (reference checkpoint.py:99-100). True = validate the file against the
    # model (names, shapes) but keep the seed-derived random init; False
    # (our default) = actually load the weights.
    hf_bootstrap_reinit: bool = False


@dataclass
class ResilienceConfig:
    """Fault-tolerance knobs (picotron_tpu/resilience/, docs/RESILIENCE.md).
    Defaults are production-safe: signals are caught, exits flush a
    checkpoint, re-running the same command resumes, and a NaN step applies
    no update. The chaos_* fields are a test/debug surface — deterministic
    fault injection at a given 1-indexed step (0 = off)."""

    # -- preemption safety --
    handle_signals: bool = True  # SIGTERM/SIGINT -> finish dispatch, save, exit 75
    save_on_exit: bool = True  # try/finally emergency save (needs save_frequency > 0)
    # Empty load_path + an existing checkpoint under save_dir resumes from it
    # (load_path "auto" asks for the same thing explicitly); re-running one
    # command continues one run. False restores start-from-scratch semantics.
    auto_resume: bool = True
    # -- loss-anomaly guard --
    # jit-side gate: a non-finite loss OR gradient applies no param/opt
    # update (jnp.where select inside the train step — numerically identity
    # on finite steps).
    nonfinite_guard: bool = True
    anomaly_policy: str = "skip"  # "skip" | "rollback" | "abort"
    anomaly_ema_beta: float = 0.95
    anomaly_zscore: float = 6.0  # spike = deviation > zscore * EMA-std
    anomaly_warmup_steps: int = 20  # steps before spike detection arms
    rollback_after: int = 3  # consecutive anomalies before a rollback
    max_rollbacks: int = 2  # then abort (a livelocked run must not loop)
    # -- retrying I/O (checkpoint saves/restores, safetensors reads) --
    io_attempts: int = 3
    io_backoff: float = 0.5  # seconds; doubles per attempt
    io_jitter: float = 0.25  # uniform [1, 1+jitter] delay scale
    # -- checkpoint replication --
    # After each primary save commits, the step directory is copied here
    # (retried, committed by atomic rename); restores fall back to the
    # mirror when every primary step is corrupt/unreadable. Point it at a
    # SECOND storage tier (different mount/bucket) or the replica is
    # decorative. "" = off.
    ckpt_mirror_dir: str = ""
    # -- emergency saves (preemption path) --
    # The preemption flush runs on a background thread (the signal path
    # stays fast) and the exit joins it with this deadline: a save wedged
    # on a dead mount delays the exit by at most this many seconds instead
    # of eating the whole preemption grace window. 0 = wait forever.
    emergency_save_timeout_s: float = 600.0
    # -- serving dispatch retry (inference/batcher.py) --
    # Each jitted serving dispatch (prefill, decode block, verify) is
    # retried this many times with exponential backoff before the batcher
    # isolates the failure to the implicated slots (finish_reason "error")
    # and keeps serving the rest.
    dispatch_attempts: int = 2
    dispatch_backoff: float = 0.05  # seconds; doubles per attempt
    # -- supervisor heartbeat (tools/supervise.py); also via $PICOTRON_HEARTBEAT --
    heartbeat_path: str = ""
    # -- cluster fault tolerance (resilience/cluster.py; docs/MULTIHOST.md) --
    # Steps between preemption-consensus rounds: a tiny jitted all-reduce of
    # every host's PreemptionGuard flag, so ANY host's SIGTERM becomes the
    # SAME coordinated emergency save + exit 75 on every host. Only active
    # with >1 JAX process (single-host behavior is byte-identical); raising
    # it trades per-boundary overhead for signal latency inside the
    # preemption grace window. 0 = off (legacy local-only check — a
    # preempted host may wedge its peers' collective save).
    consensus_interval: int = 1
    # A peer process silent (no lease renewal) this long is a dead host:
    # the ClusterMonitor exits THIS process with EXIT_CLUSTER_FAILED (77)
    # instead of wedging forever inside the next collective. 0 = off
    # (default: needs a shared cluster_dir to mean anything).
    peer_timeout_s: float = 0.0
    lease_interval_s: float = 2.0  # how often the monitor renews this host's lease
    # Shared directory for lease/done files — must be visible to every host
    # (a checkpoint-tier mount works). "" = <checkpoint.save_dir>/_cluster.
    cluster_dir: str = ""
    # -- chaos injection (resilience/chaos.py; each fires once per process) --
    chaos_raise_step: int = 0
    chaos_nan_step: int = 0
    chaos_sigterm_step: int = 0
    chaos_truncate_step: int = 0
    # -- serving chaos (resilience.chaos.ServingChaos, engine dispatch hooks;
    #    rounds are 1-indexed decode/verify dispatch invocations; 0 = off) --
    chaos_dispatch_raise_round: int = 0  # transient: raise once on round N
    # persistent: EVERY dispatch with this slot active raises — the
    # batcher's isolation path must fail exactly this slot (-1 = off)
    chaos_dispatch_fail_slot: int = -1
    chaos_latency_round: int = 0  # sleep chaos_latency_s before round N
    chaos_latency_s: float = 0.25
    chaos_poison_logits_round: int = 0  # round N's logits come back NaN
    # -- pod chaos ("RANK:STEP" strings, "" = off; fires on the process
    #    whose jax.process_index() == RANK after step STEP; a fired marker
    #    under save_dir keeps pod restarts from re-tripping the fault) --
    chaos_preempt_rank_at_step: str = ""  # SIGTERM one host: consensus drill
    chaos_kill_rank_at_step: str = ""  # SIGKILL one host: dead-peer drill
    chaos_stall_rank_at_step: str = ""  # one host sleeps: straggler drill
    chaos_stall_rank_s: float = 30.0  # how long the stalled rank sleeps


@dataclass
class SpecControllerConfig:
    """Closed-loop speculation tuning (inference/speculative.py::
    SpecController, docs/INFERENCE.md "Self-tuning speculation"). The
    first consumer of the obs registry as a CONTROL surface: the batcher
    mirrors per-slot draft-proposed/accepted counts and per-kind dispatch
    latencies into the registry, and the controller reads those live
    instruments to set ``spec_len`` per slot each round — ramping up where
    acceptance pays, ramping to 0 (speculation off; the batcher falls back
    to blocked decode when every slot is off) where it does not, and
    switching drafters per slot — with hysteresis so adversarial traffic
    cannot make it oscillate."""

    # Master switch. Inert unless inference.spec_len > 0 (there is no
    # speculation to tune); the batcher builds the controller only on
    # speculative engines.
    enabled: bool = False
    # Windowed accept rate at or above which a slot ramps its spec_len UP
    # (doubling toward inference.spec_len).
    target: float = 0.5
    # Windowed accept rate below which a slot ramps DOWN (halving toward
    # 0). The [low, target) band holds steady — the hysteresis band that
    # keeps borderline traffic from dithering.
    low: float = 0.25
    # Proposed-draft tokens per slot per evaluation window: the controller
    # re-decides only after a slot has proposed this many tokens since its
    # last decision, so one unlucky round cannot flip the policy.
    window: int = 32
    # Consecutive same-direction evaluations required before a ramp is
    # applied. With flip-flopping accept rates the direction alternates,
    # the streak never completes, and spec_len holds — test-pinned.
    hysteresis: int = 2
    # Rounds a slot sits at spec_len 0 before the controller re-probes
    # with a length-1 draft (traffic changes; a slot turned off on hard
    # traffic must be able to rediscover easy traffic).
    cooloff: int = 64
    # Minimum per-kind dispatch-latency samples (picotron_dispatch_seconds
    # histograms) before the measured verify-vs-decode cost ratio joins
    # the decision; below it the accept-rate thresholds decide alone.
    latency_min_samples: int = 16


@dataclass
class TenancyConfig:
    """Multi-tenant serving (inference/tenancy.py, docs/SERVING.md
    "Multi-tenant serving"): one replica serves many named tenants —
    each an optional LoRA adapter over the shared (possibly int8) base,
    a priority class, in-flight quotas, and TTFT/TPOT SLO targets. The
    default (no tenants, no manifest) builds no adapter pack and leaves
    every compiled program and every smoke byte-identical to the
    single-tenant engine."""

    # Inline tenant definitions (list of tenancy.Tenant dicts — see the
    # manifest schema in inference/tenancy.py). Applied after the
    # manifest, so a config can extend a shared fleet manifest.
    tenants: list = field(default_factory=list)
    # Path to a JSON tenant manifest: {"tenants": [{...}, ...]}. The
    # serve CLI's --tenant-manifest flag overrides this.
    manifest: str = ""
    # Adapter pack capacity: total adapter slots (slot 0 is the reserved
    # null adapter — base-only rows point at it and bypass exactly) and
    # the maximum adapter rank. Capacity-static: hot tenant add/remove
    # via POST /tenants writes pack slots, never recompiles a program.
    adapter_slots: int = 8
    adapter_rank: int = 16

ATTEND_IMPLS = ("auto", "dense", "flash")  # inference.attend_impl


@dataclass
class InferenceConfig:
    """Serving knobs (picotron_tpu/inference/, docs/INFERENCE.md). These
    only affect the InferenceEngine / ContinuousBatcher path; training
    ignores them."""

    # Autoregressive steps fused into one jitted decode dispatch
    # (engine.decode_block): per-slot EOS/budget stop state lives on device,
    # so the host syncs once per block instead of once per token. 1 = the
    # classic per-token loop (one dispatch per token). Also bounds admission
    # latency: the batcher admits/retires only at block boundaries.
    decode_block_len: int = 8
    # Data-parallel shards of one logical engine (docs/INFERENCE.md
    # "dp-sharded batching"): the slot axis — tokens, sampling state,
    # lengths, KV cache / paged pool — shards over a ('dp', 'tp') mesh
    # while params stay replicated across dp, so ONE jitted dispatch
    # advances dp x slots_per_shard slots with zero cross-shard traffic
    # on the decode/verify hot path. 1 (default) = today's tp-only mesh,
    # every existing smoke byte-identical. Requires slots % dp_size == 0
    # and (paged) kv_num_pages % dp_size == 0.
    dp_size: int = 1
    # Weight storage format for serving: "bf16" (the model's param dtype,
    # the bit-pinned default — every existing smoke is unchanged) or
    # "int8" = per-output-channel absmax quantization of every matmul
    # weight (wq/wk/wv/wo, w_gate/w_up/w_down, lm_head; embeddings and
    # norms stay full precision), applied at load
    # (checkpoint.load_params / load_hf_safetensors) so a 7B-class
    # checkpoint's weights land on device at ~half the bf16 bytes.
    # Matmuls consume the int8 storage directly through the fused
    # dequant kernel (ops/pallas/quant_matmul.py) — no dequantized
    # weight copy ever exists; scales shard over 'tp' with their output
    # channels. Generations are allclose to bf16 (and pinned exactly
    # against the fake-quant reference — tests/test_quant_weights.py).
    weight_dtype: str = "bf16"
    # KV cache storage dtype: "auto" = the model's param dtype; "int8" =
    # per-row per-kv-head absmax-quantized storage with fp32 scales
    # (kv_cache.quantize_kv) — ~2x the slots or context at the same HBM
    # budget, dequantized inside decode attention.
    kv_cache_dtype: str = "auto"
    # KV cache memory layout: "contiguous" = every slot owns a
    # max_seq_len strip (the bit-pinned default); "paged" = block-table
    # indirection over a global pool of fixed-size KV pages
    # (inference/paged_kv.py) with refcounted prefix sharing and
    # copy-on-write — HBM tracks LIVE tokens instead of slots x window,
    # and identical prompt prefixes are stored (and prefilled) once.
    # Generations are pinned identical to contiguous
    # (tests/test_paged_kv.py). Contiguous stays the default: the decode
    # attend over a strip is the kernel every Llama cell's numbers rest
    # on, and since PR 49 the layout reuses prompts too. A Llama-block
    # engine keeps finished prompts' whole pages in a side store
    # (kv_store_pages below) and COPIES the retained prefix of a later
    # prompt into its strip instead of prefilling it again; "paged" shares
    # such pages in place and also packs live tokens, at the price of its
    # attends (PERF.md, PR 26).
    kv_layout: str = "contiguous"
    # Rows per KV page (paged layout only). Small pages waste less
    # capacity per sequence and fork prefixes at finer grain; large pages
    # make each kernel DMA deeper. Power of two >= 8 (the flash kernel's
    # sublane quantum).
    kv_page_len: int = 16
    # Pool size in pages (paged layout only). 0 = auto: one reserved
    # NULL page + slots * ceil(max_seq_len / kv_page_len) — capacity
    # parity with the contiguous layout; raise it to oversubscribe slots
    # against short typical sequences, shrink it to cap HBM.
    kv_num_pages: int = 0
    # Pages of the contiguous layout's prefix store (docs/SERVING.md
    # "Prompt reuse on the contiguous layout"; kv_page_len rows each, the
    # NULL page among them). 0 = auto: twice the strips' rows (2 x slots x
    # ceil(max_seq_len / kv_page_len) pages), fewer or none where the
    # device could not hold them beside the weights and the strips; a
    # positive value is the pool as given; -1 = no store. The store exists
    # only where engine._store_pages says (a Llama block, contiguous
    # layout, cache in the model's dtype, serial admission): elsewhere the
    # field is not read.
    kv_store_pages: int = 0
    # Radix prefix cache (paged layout only): prompt pages are kept in a
    # token-keyed trie after prefill and new requests reuse (refcount,
    # skip prefilling) their longest cached prefix, copy-on-write at the
    # fork point. False = pure paging, no sharing.
    prefix_cache: bool = True
    # Per-page storage policy (paged layout only): "uniform" = every page
    # stores kv_cache_dtype (the pinned default); "hot_bf16" = pages with
    # more than one holder — radix-shared prefixes, forked slots — are
    # READ at full precision while exclusively-held pages (cold unique
    # tails, the bulk of a long generation) are read as int8 + per-row
    # scales, so the shared prefix keeps full fidelity and the tail moves
    # ~half the bytes per attend walk. Requires kv_layout: "paged" and is
    # mutually exclusive with kv_cache_dtype: "int8" (the policy manages
    # its own quantized representation). Handled by both the dense gather
    # and the flash DMA read paths (inference/paged_kv.py).
    kv_page_policy: str = "uniform"
    # Disaggregated serving role (tools/serve.py, docs/SERVING.md
    # "Disaggregated prefill/decode"): "both" (default — one replica runs
    # admission, prefill, and decode exactly as before; every existing
    # smoke is unchanged); "prefill" — the replica runs admission +
    # chunked/paged prefill only and hands finished KV pages off through
    # POST /kv/export (its /generate sheds with 503); "decode" — the
    # replica seats imported pages (POST /kv/import, /generate's "kv"
    # field) and runs the decode/spec loop, so a long prompt's prefill
    # never steals one of its dispatch rounds (it still self-prefills
    # plain requests as the failover fallback). Any role but "both"
    # requires kv_layout: "paged" — the page pool IS the handoff unit.
    role: str = "both"
    # Prompts longer than this prefill as a sequence of fixed-width chunk
    # dispatches writing K/V straight into the target slot
    # (engine.prefill_chunked): O(1) compiled shapes in prompt length and
    # flat peak activation memory. Prompts at or under it keep the
    # pow-2-bucketed one-shot prefill.
    prefill_chunk: int = 512
    # Which kernel serves KV-cache attention on the decode/verify/chunked-
    # prefill hot path. "auto" (the shipped default, as
    # model.attention_impl: auto is for training): on a TPU the plain
    # decode step (one query a slot against a contiguous bfloat16 cache of
    # whole-lane rows) runs the stacked flash-decode kernel
    # (ops/pallas/decode_attention.py::flash_decode_stacked: K and V read
    # out of the stacked leaf where they lie, one pass, live rows only);
    # every other call (prefill chunks, verify, the mixed lane, int8 and
    # paged caches) and every call off a TPU runs "dense". "dense" = the
    # masked einsum+softmax over the whole cache window everywhere
    # (kv_cache.decode_attention, the bit-pinned reference); "flash" = the
    # Pallas flash-decode kernels everywhere: online softmax over KV blocks
    # bounded by each slot's LIVE length, int8 K/V dequantized inside the
    # kernel (no whole-cache fp32 materialization), GQA-native. Off a TPU
    # "flash" runs in Pallas interpret mode (slow: a parity/test surface,
    # not a serving one); allclose-pinned against dense in
    # tests/test_decode_kernel.py.
    attend_impl: str = "auto"
    # Fused on-device sampling epilogue: the prefill / chunked-prefill /
    # decode_step dispatches sample their next token INSIDE the jitted
    # program (temperature -> top-k -> top-p -> categorical, the same
    # fused filter sampling.sample runs, sanitize_logits applied first),
    # so only sampled token ids [B] cross to the host instead of full
    # [B, vocab] fp32 logits. Seeded-identical to the host sampler: the
    # batcher passes the exact PRNG key the host path would have drawn.
    # False (default) keeps the host-side sampling path — the bit-pinned
    # staging default until the epilogue is A/B'd on a chip, like
    # attend_impl/kv_layout before it. (decode_block and verify always
    # sampled on device; this key completes the story for the remaining
    # logits round-trips.)
    sample_on_device: bool = False
    # Speculative decoding (inference/speculative.py, engine.verify): number
    # of tokens the drafter proposes per slot per dispatch. One jitted
    # verify pass scores all spec_len+1 positions, accepts the matching
    # draft prefix (exact match for greedy, distribution-preserving
    # rejection sampling otherwise) and emits 1..spec_len+1 tokens per
    # dispatch. 0 (default) = off: the batcher drives decode_block instead.
    spec_len: int = 0
    # Longest suffix n-gram the built-in prompt-lookup drafter matches
    # against the slot's own token history (tried spec_ngram down to 1) to
    # propose continuations. Only consulted when spec_len > 0.
    spec_ngram: int = 3
    # Which draft model proposes speculative continuations (spec_len > 0):
    # "ngram" = the model-free prompt-lookup drafter (host-side, free);
    # "learned" = the EAGLE-style learned drafter
    # (inference/speculative.py::LearnedDrafter) — a tiny head over the
    # target's own last hidden state that shares the target's embedding
    # and lm_head weights (no separate checkpoint; optional tiny-head
    # params ride a params tree), drafting spec_len tokens in one small
    # jitted dispatch. "learned" makes the engine plumb the last hidden
    # state out of every decode/verify dispatch (the return_hidden hook).
    drafter: str = "ngram"
    # Token window the n-gram drafter's suffix match scans (most recent N
    # history tokens). 0 = unbounded. The drafter's index is incremental
    # (append-only) either way; the window caps how far back a match may
    # land, keeping long-running slots' lookups O(1) per round.
    spec_history_window: int = 0
    # Closed-loop per-slot spec_len tuning — see SpecControllerConfig.
    spec_controller: SpecControllerConfig = field(
        default_factory=SpecControllerConfig)
    # Multi-tenant serving — see TenancyConfig.
    tenancy: TenancyConfig = field(default_factory=TenancyConfig)
    # Zero-bubble overlapped scheduling (docs/INFERENCE.md "Overlapped
    # scheduling"): the batcher issues dispatch N+1 BEFORE syncing
    # dispatch N, so token delivery / drafting / admission run while the
    # device executes the next round. Requires the per-slot key schedule
    # (key_schedule resolves to "slot" under "auto") so sampled streams
    # stay bit-identical to overlap-off. False (default) keeps the
    # issue-then-sync loop byte-identical to today's smokes.
    overlap: bool = False
    # PRNG key schedule for sampled decode/verify tokens:
    # "round" — one fresh key per dispatch round (the historical
    #   schedule; streams depend on round structure, so it cannot
    #   overlap);
    # "slot"  — one base key per ADMITTED request, token at position p
    #   keyed fold_in(base, p-1): streams depend only on (base key,
    #   prompt, logits), independent of round boundaries, draft
    #   contents, and controller decisions;
    # "auto" (default) — "slot" when overlap or mixed_dispatch is on,
    #   else "round".
    key_schedule: str = "auto"
    # Stall-free mixed prefill–decode dispatch (docs/INFERENCE.md "Mixed
    # prefill–decode dispatch"): every decode/verify dispatch also
    # carries one fixed-width prefill LANE (prefill_chunk tokens, padded
    # and masked when idle so the compiled shape never changes), so
    # admissions stream in without stalling active decode slots on solo
    # prefill dispatches. Requires the per-slot key schedule
    # (key_schedule resolves to "slot" under "auto") so sampled streams
    # stay bit-identical to mixed-off. False (default) keeps the serial
    # prefill path byte-identical to today's scheduler.
    mixed_dispatch: bool = False

    def __post_init__(self):
        # from_dict hands nested blocks through as plain dicts; coerce so
        # cfg.inference.spec_controller.target always works (unknown keys
        # ignored, matching Config.from_dict's build())
        if isinstance(self.spec_controller, dict):
            known = {f.name for f in
                     dataclasses.fields(SpecControllerConfig)}
            self.spec_controller = SpecControllerConfig(
                **{k: v for k, v in self.spec_controller.items()
                   if k in known})
        if isinstance(self.tenancy, dict):
            known = {f.name for f in dataclasses.fields(TenancyConfig)}
            self.tenancy = TenancyConfig(
                **{k: v for k, v in self.tenancy.items() if k in known})
    # Graceful degradation for the flash attend path: when a dispatch
    # fails under attend_impl "flash" (or "auto" on a TPU, where the plain
    # decode step runs the kernel), log once, rebuild the engine's
    # compiled programs on "dense", and keep serving — for the REST OF THE
    # PROCESS (new engines start dense too; a kernel that broke once is
    # not re-trusted mid-serve). False = the failure propagates.
    attend_fallback: bool = True


@dataclass
class ObsConfig:
    """Observability knobs (picotron_tpu/obs/, docs/OBSERVABILITY.md).
    The default is ON: recording counters/spans costs nanoseconds per
    event and never touches stdout, so smoke output is unchanged either
    way; ``enabled: false`` swaps in null instruments for a zero-
    bookkeeping hot path. Scope: the switch governs the engine/batcher/
    serve/train instruments built from THIS config; ``comm_trace``'s
    per-collective instant spans are debug output gated by
    ``PICOTRON_VERBOSE>=1`` alone (off by default, and already paying a
    stderr line per collective when on)."""

    enabled: bool = True
    # Finished spans the process trace ring retains (oldest dropped).
    span_ring: int = 4096
    # Raw samples each histogram keeps for exact /statz percentiles.
    sample_window: int = 4096
    # Per-step training metrics JSONL path ("" = off). The supervisor/
    # scheduler export $PICOTRON_METRICS_JSONL next to the run log, which
    # wins over this field — same precedence as the heartbeat path.
    # Controller process only; extract_metrics.py prefers this file over
    # regex-scraping the log.
    metrics_jsonl: str = ""
    # Chrome-trace JSON dumped from the span ring when train() exits
    # ("" = off). Validate/inspect with tools/trace_dump.py.
    trace_path: str = ""
    # On-demand profiler captures (SIGUSR2 on the CLIs, POST /profilez on
    # the serving front end): jax.profiler traces land here, each capture
    # timed at profile_seconds.
    profile_dir: str = "profiles"
    profile_seconds: float = 5.0


@dataclass
class RouterConfig:
    """Multi-replica serving fabric knobs (``tools/router.py``,
    docs/SERVING.md "Multi-replica fabric"). Deliberately NOT a section of
    ``Config``: the router fronts a FLEET of serve.py replicas (each with
    its own experiment config) and is configured per deployment — one JSON
    object loaded with ``RouterConfig.from_dict`` (unknown keys ignored,
    same policy as ``Config``) or plain CLI flags."""

    # -- health probing (per-replica prober thread) --
    probe_interval_s: float = 1.0  # closed-state probe cadence
    probe_timeout_s: float = 2.0  # per-HTTP-call probe deadline
    # -- circuit breaker --
    breaker_failures: int = 3  # consecutive hard failures -> open
    # open-state reprobe ladder (resilience.retry): first delay, doubling
    # per failed reprobe, capped; a successful reprobe -> half-open, one
    # trial request decides closed vs open again.
    breaker_backoff_s: float = 1.0
    breaker_backoff_max_s: float = 30.0
    breaker_probe_attempts: int = 6  # reprobes per retry() ladder cycle
    # -- load scraping / scoring --
    # a replica whose last good /metrics scrape is older than this falls
    # out of the candidate set (stale = unknown load = unplaceable)
    scrape_stale_s: float = 10.0
    load_queue_weight: float = 1.0  # per queued request (+ router inflight)
    load_slot_weight: float = 0.5  # per active slot
    load_pool_weight: float = 4.0  # per unit of KV pool utilization [0,1]
    load_ttft_weight: float = 2.0  # per second of TTFT p95
    # -- prefix affinity --
    # prompt prefixes are hashed at this page alignment (match the fleet's
    # inference.kv_page_len so the hash key is exactly the radix-shareable
    # page run); the affinity (rendezvous) pick wins while its load score
    # is within affinity_load_slack of the least-loaded candidate.
    affinity_page_len: int = 16
    affinity_load_slack: float = 4.0
    # -- prefill/decode disaggregation (docs/SERVING.md) --
    # When the fleet holds role=prefill replicas, route each prompt's
    # prefill to its affinity prefill worker (POST /kv/export), stream
    # the finished KV pages to the decode placement, and splice the token
    # stream — a failed/severed export falls back to self-prefill at the
    # decode placement (the replay bookkeeping's path). False = ignore
    # prefill workers for orchestration (they still probe/scrape).
    disagg: bool = True
    # On a placement that escaped its affinity owner, ask the owner for
    # the longest cached page-aligned prefix (GET /kv/pages) and import
    # it at the chosen replica (POST /kv/import) before generating —
    # shared system prompts prefill once per CLUSTER. Soft: any failure
    # just skips the fetch.
    prefix_fetch: bool = True
    # Deadline for one /kv/export round trip (the prefill itself runs
    # inside it, so this is a prefill budget, not a probe timeout).
    handoff_timeout_s: float = 120.0
    # -- per-request bounds --
    place_attempts: int = 3  # placements that never streamed (shed/refused)
    replay_budget: int = 2  # mid-stream failovers (replays) per request
    connect_timeout_s: float = 5.0
    # no token for this long mid-stream reads as a wedged replica (the
    # failover trigger for stalls the replica's own watchdog missed)
    stream_idle_timeout_s: float = 60.0
    retry_after_s: int = 2  # Retry-After when no replica is eligible

    def validate(self) -> None:
        for name in ("probe_interval_s", "probe_timeout_s",
                     "breaker_backoff_s", "breaker_backoff_max_s",
                     "scrape_stale_s", "connect_timeout_s",
                     "stream_idle_timeout_s", "handoff_timeout_s"):
            if getattr(self, name) <= 0:
                raise ValueError(f"router.{name} must be > 0")
        for name in ("breaker_failures", "breaker_probe_attempts",
                     "place_attempts", "retry_after_s"):
            if getattr(self, name) < 1:
                raise ValueError(f"router.{name} must be >= 1")
        if self.replay_budget < 0:
            raise ValueError("router.replay_budget must be >= 0 (0 = a "
                             "mid-stream death fails the request)")
        if self.breaker_backoff_max_s < self.breaker_backoff_s:
            raise ValueError(
                f"router.breaker_backoff_max_s "
                f"({self.breaker_backoff_max_s}) must be >= "
                f"breaker_backoff_s ({self.breaker_backoff_s})")
        p = self.affinity_page_len
        if p < 8 or p & (p - 1):
            # the same quantum rule as inference.kv_page_len: the hash key
            # must be a whole page run or affinity lands shared prefixes on
            # different replicas than the radix cache can reuse
            raise ValueError(
                f"router.affinity_page_len must be a power of two >= 8 "
                f"(match the fleet's inference.kv_page_len), got {p}")
        for name in ("load_queue_weight", "load_slot_weight",
                     "load_pool_weight", "load_ttft_weight",
                     "affinity_load_slack"):
            if getattr(self, name) < 0:
                raise ValueError(f"router.{name} must be >= 0")

    @classmethod
    def from_dict(cls, raw: dict[str, Any]) -> "RouterConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        cfg = cls(**{k: v for k, v in raw.items() if k in known})
        cfg.validate()
        return cfg


@dataclass
class FleetConfig:
    """Elastic fleet controller knobs (``tools/fleet.py``, docs/SERVING.md
    "Elastic fleet"). Like ``RouterConfig``, deliberately NOT a section of
    ``Config``: the controller owns a fleet of serve.py workers (each with
    its own experiment config) and is configured per deployment — one JSON
    object loaded with ``FleetConfig.from_dict`` (unknown keys ignored) or
    plain CLI flags.

    The control loop scrapes every worker's ``/metrics`` + ``/readyz`` each
    ``scrape_interval_s`` and walks a fixed decision ladder per role:
    replace dead workers first (budget-gated, never cooloff-gated — lost
    capacity must not wait), then grow on a sustained high-watermark
    breach, then drain on a sustained all-low reading. "Sustained" is
    ``hysteresis`` consecutive ticks; grow/drain additionally respect a
    per-role ``cooloff_s`` so one spike cannot thrash the fleet (the
    SpecController discipline, lifted to fleet scale)."""

    # -- control loop --
    scrape_interval_s: float = 1.0  # tick cadence (scrape + decide)
    scrape_timeout_s: float = 2.0  # per-HTTP-call scrape deadline
    # consecutive breached ticks (or failed worker probes) before acting
    hysteresis: int = 2
    cooloff_s: float = 10.0  # min seconds between grow/drain per role
    # -- watermarks (grow when ANY high is breached; drain only when ALL
    # signals sit below their lows) --
    queue_high: float = 8.0  # queued requests per worker (prefill queue
    # depth on prefill workers — the signal a disaggregated fleet watches)
    queue_low: float = 1.0
    pool_high: float = 0.85  # KV pool utilization [0, 1]
    pool_low: float = 0.30
    ttft_slo_s: float = 0.0  # TTFT p95 above this -> grow (0 = off)
    # -- fleet bounds (per role) --
    min_workers: int = 1
    max_workers: int = 8
    # -- dead-worker replacement ladder (reuses the _RestartBudget
    # semantics from tools/supervise.py: bounded attempts, exponential
    # backoff, healthy-uptime replenishment) --
    max_replaces: int = 3
    replace_backoff_s: float = 0.5
    replace_backoff_max_s: float = 30.0
    healthy_reset_s: float = 600.0
    launch_attempts: int = 2  # resilience.retry attempts per launch
    # -- drain protocol --
    drain_timeout_s: float = 120.0  # POST /drain -> worker exit deadline
    # on a scale-down drain, export the victim's hottest radix prefixes
    # to a surviving worker through the PR 15 page transport (GET
    # /kv/prefixes -> POST /kv/pages -> POST /kv/import) so the drained
    # worker's cache is not lost to the cluster; soft — any failure just
    # skips the export
    export_prefixes: bool = True
    export_prefix_limit: int = 4  # hottest cached prefixes per drain

    def validate(self) -> None:
        for name in ("scrape_interval_s", "scrape_timeout_s", "cooloff_s",
                     "replace_backoff_s", "replace_backoff_max_s",
                     "drain_timeout_s"):
            if getattr(self, name) <= 0:
                raise ValueError(f"fleet.{name} must be > 0")
        for name in ("hysteresis", "min_workers", "launch_attempts",
                     "export_prefix_limit"):
            if getattr(self, name) < 1:
                raise ValueError(f"fleet.{name} must be >= 1")
        if self.max_replaces < 0:
            raise ValueError("fleet.max_replaces must be >= 0 (0 = a dead "
                             "worker is never replaced)")
        if self.max_workers < self.min_workers:
            raise ValueError(
                f"fleet.max_workers ({self.max_workers}) must be >= "
                f"min_workers ({self.min_workers})")
        if self.replace_backoff_max_s < self.replace_backoff_s:
            raise ValueError(
                f"fleet.replace_backoff_max_s ({self.replace_backoff_max_s}) "
                f"must be >= replace_backoff_s ({self.replace_backoff_s})")
        for name in ("queue_high", "queue_low", "pool_high", "pool_low",
                     "ttft_slo_s", "healthy_reset_s"):
            if getattr(self, name) < 0:
                raise ValueError(f"fleet.{name} must be >= 0")
        if self.queue_low > self.queue_high:
            raise ValueError(
                f"fleet.queue_low ({self.queue_low}) must be <= queue_high "
                f"({self.queue_high}) — the hysteresis band inverts")
        if self.pool_low > self.pool_high:
            raise ValueError(
                f"fleet.pool_low ({self.pool_low}) must be <= pool_high "
                f"({self.pool_high}) — the hysteresis band inverts")

    @classmethod
    def from_dict(cls, raw: dict[str, Any]) -> "FleetConfig":
        known = {f.name for f in dataclasses.fields(cls)}
        cfg = cls(**{k: v for k, v in raw.items() if k in known})
        cfg.validate()
        return cfg


@dataclass
class LoggingConfig:
    use_wandb: bool = False
    run_name: str = "picotron-tpu"
    log_frequency: int = 1
    # capture a jax.profiler trace for steps [profile_start, profile_stop)
    # into profile_dir (SURVEY.md §5.1 rebuild note); 0 = off
    profile_start: int = 0
    profile_stop: int = 0
    profile_dir: str = "profiles"


# The flagship benchmark model (reference README.md:7 headline:
# SmolLM-1.7B at ~50% MFU on 8xH100). Shared by chip_smoke.py, the driver
# entry (__graft_entry__.py) and tests/test_tools.py so all measure the same
# model.
SMOLLM_1_7B = dict(
    name="HuggingFaceTB/SmolLM-1.7B", num_hidden_layers=24,
    num_attention_heads=32, num_key_value_heads=32, hidden_size=2048,
    intermediate_size=8192, vocab_size=49152, max_position_embeddings=2048,
    dtype="bfloat16", attention_impl="auto",
)


@dataclass
class Config:
    distributed: DistributedConfig = field(default_factory=DistributedConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    training: TrainingConfig = field(default_factory=TrainingConfig)
    dataset: DatasetConfig = field(default_factory=DatasetConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    logging: LoggingConfig = field(default_factory=LoggingConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    inference: InferenceConfig = field(default_factory=InferenceConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)

    @property
    def world_size(self) -> int:
        d = self.distributed
        return d.tp_size * d.cp_size * d.pp_size * d.dp_size

    @property
    def global_batch_size(self) -> int:
        """micro_batch * grad_acc * dp  (reference data.py:17)."""
        return (
            self.training.micro_batch_size
            * self.training.gradient_accumulation_steps
            * self.distributed.dp_size
        )

    @property
    def tokens_per_step(self) -> int:
        return self.global_batch_size * self.training.seq_length

    def validate(self, for_training: bool = False) -> None:
        """Divisibility constraints, surfaced as errors the way the reference
        uses asserts (train.py:85-86, model.py:94-95, tensor_parallel.py:226).
        What is a model block's own (its published keys, and what it cannot
        do yet) is not checked here: the block of ``model_type`` is looked
        up in ``models.BLOCKS``, an unknown one refused with that table's
        names, and the module's ``validate(cfg, for_training)`` called where
        it has one. ``for_training`` is what the training entry points pass
        (train_step.init_state / build_train_step): a block that only
        serves refuses there, by name."""
        d, m, t = self.distributed, self.model, self.training
        if t.seq_length % d.cp_size != 0:
            raise ValueError(f"seq_length {t.seq_length} % cp_size {d.cp_size} != 0")
        if d.cp_zigzag and t.seq_length % (2 * d.cp_size) != 0:
            raise ValueError(
                f"cp_zigzag needs seq_length % (2*cp_size) == 0, got "
                f"{t.seq_length} % {2 * d.cp_size}")
        if d.cp_impl not in ("ring", "ulysses"):
            raise ValueError(f"unknown cp_impl {d.cp_impl!r} (ring|ulysses)")
        if d.cp_impl == "ulysses" and d.cp_size > 1:
            if d.cp_zigzag:
                raise ValueError(
                    "cp_impl='ulysses' is incompatible with cp_zigzag (the "
                    "all-to-all layout is load-balanced by construction)")
            if (m.num_attention_heads // d.tp_size) % d.cp_size != 0:
                raise ValueError(
                    f"cp_impl='ulysses' needs local heads "
                    f"({m.num_attention_heads} / tp {d.tp_size}) divisible "
                    f"by cp_size {d.cp_size}")
        if d.tp_sequence_parallel and (
                t.seq_length // d.cp_size) % d.tp_size != 0:
            raise ValueError(
                f"tp_sequence_parallel needs the cp-local sequence "
                f"({t.seq_length} / cp {d.cp_size}) divisible by tp_size "
                f"{d.tp_size}")
        if m.num_attention_heads % d.tp_size != 0:
            raise ValueError(f"num_attention_heads {m.num_attention_heads} % tp_size {d.tp_size} != 0")
        if m.num_key_value_heads % d.tp_size != 0:
            raise ValueError(f"num_key_value_heads {m.num_key_value_heads} % tp_size {d.tp_size} != 0")
        if m.num_attention_heads % m.num_key_value_heads != 0:
            raise ValueError("num_attention_heads must be a multiple of num_key_value_heads")
        if m.vocab_size % d.tp_size != 0:
            raise ValueError(f"vocab_size {m.vocab_size} % tp_size {d.tp_size} != 0")
        if m.hidden_size % m.num_attention_heads != 0:
            raise ValueError("hidden_size must be divisible by num_attention_heads")
        if m.num_hidden_layers < d.pp_size:
            # Uneven splits are supported (remainder layers on the earliest
            # stages, reference pipeline_parallel.py:33-36, via a masked
            # padded layer stack — models/llama.py::pp_layer_layout), but
            # every stage must hold at least one real layer.
            raise ValueError(
                f"num_hidden_layers {m.num_hidden_layers} < pp_size {d.pp_size}")
        if d.pp_size > 1 and t.gradient_accumulation_steps < 1:
            raise ValueError("pipeline parallelism needs >= 1 microbatch")
        if d.pp_engine not in ("afab", "1f1b"):
            raise ValueError(f"unknown pp_engine {d.pp_engine!r} (afab|1f1b)")
        if d.stage_gating not in ("auto", "cond", "where"):
            raise ValueError(
                f"unknown stage_gating {d.stage_gating!r} (auto|cond|where)")
        if d.check_vma:
            if d.pp_engine == "afab" and d.pp_size > 1:
                raise ValueError(
                    "check_vma=True is incompatible with pp_engine='afab': "
                    "jax's scan transpose does not type varying manual axes "
                    "yet (differentiating the forward pipeline trips it); "
                    "use the 1f1b engine or turn the checker off")
            if d.pp_size > 1 and (
                    d.stage_gating == "cond"
                    or (d.stage_gating == "auto" and not d.use_cpu)):
                raise ValueError(
                    "check_vma=True is incompatible with lax.cond stage "
                    "gating (the checker's auto-inserted pvary transposes "
                    "put real psums inside single-stage branches, which "
                    "deadlocks); set stage_gating='where' — or, on a CPU "
                    "box, set use_cpu: true in the distributed config "
                    "section, which resolves the 'auto' gating to "
                    "where-masking")
        if d.stage_gating == "cond" and d.use_cpu and d.tp_size > 1:
            # the gated branches carry tp collectives, and the XLA CPU
            # runtime's rendezvous intermittently aborts when a collective
            # is reached by a subset of devices (models/llama.py::
            # _stage_gating) — surface it at load, not mid-run
            raise ValueError(
                "stage_gating='cond' on a CPU mesh requires tp_size == 1 "
                "(gated tp collectives can abort the XLA CPU rendezvous); "
                "use 'auto' or 'where'")
        if d.pp_interleave < 1:
            raise ValueError("pp_interleave must be >= 1")
        if d.pp_interleave > 1:
            if d.pp_size == 1:
                # Without this, the interleaved layout path still runs in
                # init_params and dies in pp_layer_layout with a bare assert.
                raise ValueError("pp_interleave > 1 requires pp_size > 1")
            if d.pp_engine != "1f1b":
                raise ValueError("pp_interleave > 1 requires pp_engine='1f1b'")
            if m.num_hidden_layers % (d.pp_size * d.pp_interleave) != 0:
                raise ValueError(
                    f"pp_interleave needs num_hidden_layers "
                    f"({m.num_hidden_layers}) divisible by pp_size * "
                    f"pp_interleave ({d.pp_size} * {d.pp_interleave})")
            if t.gradient_accumulation_steps % d.pp_size != 0:
                raise ValueError(
                    f"pp_interleave needs gradient_accumulation_steps "
                    f"({t.gradient_accumulation_steps}) divisible by pp_size "
                    f"({d.pp_size}) (microbatch groups cycle the chunks)")
        if d.fsdp:
            if d.zero1:
                raise ValueError(
                    "fsdp and zero1 are mutually exclusive (FSDP already "
                    "shards the layer stack's params, grads, and state)")
            if m.hidden_size % d.dp_size != 0:
                raise ValueError(
                    f"fsdp needs hidden_size ({m.hidden_size}) divisible by "
                    f"dp_size ({d.dp_size}) — every layer param shards on an "
                    f"H-sized axis")
        # what the block of ``model_type`` needs of its keys and cannot do
        # yet is the block module's to say (``models/support.py``); on
        # demand: the blocks import this module
        from picotron_tpu.models import model_module

        block_validate = getattr(model_module(m), "validate", None)
        if block_validate is not None:
            block_validate(self, for_training)
        if m.attention_impl not in ("auto", "sdpa", "flash"):
            raise ValueError(
                f"unknown attention_impl {m.attention_impl!r} (auto|sdpa|flash)")
        if m.loss_impl not in ("auto", "fused", "gathered", "vocab_parallel"):
            raise ValueError(
                f"unknown loss_impl {m.loss_impl!r} "
                "(auto|fused|gathered|vocab_parallel)")
        if t.steps_per_call < 1:
            raise ValueError("steps_per_call must be >= 1")
        if t.num_samples is not None and t.num_samples < 1:
            raise ValueError("num_samples must be >= 1 when set")
        if self.dataset.max_in_memory_tokens < 1:
            raise ValueError("max_in_memory_tokens must be >= 1")
        if t.lr_schedule not in ("constant", "cosine", "linear"):
            raise ValueError(
                f"unknown lr_schedule {t.lr_schedule!r} (constant|cosine|linear)")
        if t.lr_warmup_steps < 0:
            raise ValueError("lr_warmup_steps must be >= 0")
        if not 0.0 <= t.lr_min_ratio <= 1.0:
            raise ValueError("lr_min_ratio must be in [0, 1]")
        if t.lr_decay_steps is not None and t.lr_decay_steps <= 0:
            raise ValueError("lr_decay_steps must be > 0 when set")
        if t.lr_schedule in ("cosine", "linear"):
            # the decay horizon defaults to total_train_steps
            # (train_step.lr_schedule); either way a horizon <= warmup would
            # silently clamp into a near-instant decay
            horizon = (t.lr_decay_steps if t.lr_decay_steps is not None
                       else t.total_train_steps)
            if horizon <= t.lr_warmup_steps:
                which = ("lr_decay_steps" if t.lr_decay_steps is not None
                         else "total_train_steps")
                raise ValueError(
                    f"{which} ({horizon}) must exceed lr_warmup_steps "
                    f"({t.lr_warmup_steps}) for a decaying schedule")
        if t.remat not in ("none", "full", "save_attn", "offload"):
            raise ValueError(
                f"unknown remat {t.remat!r} (none|full|save_attn|offload)")
        if t.grad_accum_dtype not in ("float32", "param"):
            raise ValueError(
                f"unknown grad_accum_dtype {t.grad_accum_dtype!r} (float32|param)")
        if m.flash_layout not in ("folded", "bshd", "merged"):
            raise ValueError(
                f"unknown flash_layout {m.flash_layout!r} "
                f"(folded|bshd|merged)")
        if m.flash_layout == "merged":
            from picotron_tpu.ops.pallas.flash_attention import LANE

            if m.head_dim % LANE:
                raise ValueError(
                    f"flash_layout 'merged' needs head_dim % {LANE} == 0 "
                    f"(Mosaic lane tiling); got head_dim={m.head_dim} — "
                    f"use 'folded'")
        for name, b in (("flash_block_q", m.flash_block_q),
                        ("flash_block_k", m.flash_block_k)):
            # Powers of two keep the kernel's halve-until-divides fallback
            # (_pick_block) landing on real tile sizes instead of degrading
            # to 1-row blocks (e.g. 24 -> 3 -> 1). The kernel accepts small
            # tiles (ring half-blocks generate them); for full lane
            # utilization prefer block_k >= 128 and block_q >= 8 x dtype
            # packing (the 512x512 defaults are the measured optimum).
            if b is not None and (b < 8 or b & (b - 1) != 0):
                raise ValueError(
                    f"{name} must be a power of two >= 8, got {b}")
        # grad_accum_dtype='param' is valid on every topology: the pipeline
        # engines accept acc_dtype (fp32 default = the reference's main_grad
        # policy; param dtype halves the accumulator + the dp sync wire and
        # is what lets 7B fit 16 GB v5e chips at tp2/pp2 — docs/PROJECTION.md)
        if t.seq_length > m.max_position_embeddings:
            raise ValueError(
                f"seq_length {t.seq_length} > max_position_embeddings "
                f"{m.max_position_embeddings}")
        r = self.resilience
        if r.anomaly_policy not in ("skip", "rollback", "abort"):
            raise ValueError(
                f"unknown anomaly_policy {r.anomaly_policy!r} "
                "(skip|rollback|abort)")
        if r.anomaly_policy == "rollback" and self.checkpoint.save_frequency <= 0:
            raise ValueError(
                "anomaly_policy='rollback' needs checkpoint.save_frequency > 0 "
                "(there is nothing to roll back to without checkpoints)")
        if not 0.0 < r.anomaly_ema_beta < 1.0:
            raise ValueError("anomaly_ema_beta must be in (0, 1)")
        if r.io_attempts < 1:
            raise ValueError("io_attempts must be >= 1")
        if r.io_backoff < 0 or r.io_jitter < 0:
            raise ValueError("io_backoff and io_jitter must be >= 0")
        if r.dispatch_attempts < 1:
            raise ValueError("dispatch_attempts must be >= 1")
        if r.dispatch_backoff < 0:
            raise ValueError("dispatch_backoff must be >= 0")
        if r.emergency_save_timeout_s < 0:
            raise ValueError(
                "emergency_save_timeout_s must be >= 0 (0 = wait forever)")
        if r.rollback_after < 1:
            raise ValueError("rollback_after must be >= 1")
        if r.max_rollbacks < 0:
            raise ValueError("max_rollbacks must be >= 0")
        inf = self.inference
        if inf.decode_block_len < 1:
            raise ValueError("inference.decode_block_len must be >= 1")
        if inf.dp_size < 1:
            raise ValueError(
                "inference.dp_size must be >= 1 (1 = tp-only serving "
                "mesh; N shards one logical engine's slot axis over a "
                "('dp', 'tp') mesh of N x tp_size devices)")
        if inf.prefill_chunk < 1:
            raise ValueError("inference.prefill_chunk must be >= 1")
        if inf.weight_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"unknown inference.weight_dtype {inf.weight_dtype!r} "
                "(bf16|int8) — set 'int8' for per-channel quantized "
                "weights served through the fused dequant matmul, or "
                "keep the 'bf16' full-precision default")
        if inf.kv_cache_dtype not in ("auto", "int8"):
            raise ValueError(
                f"unknown inference.kv_cache_dtype {inf.kv_cache_dtype!r} "
                "(auto|int8)")
        if inf.kv_layout not in ("contiguous", "paged"):
            raise ValueError(
                f"unknown inference.kv_layout {inf.kv_layout!r} "
                "(contiguous|paged)")
        if inf.kv_page_len < 8 or inf.kv_page_len & (inf.kv_page_len - 1):
            # powers of two keep page/window math exact and respect the
            # flash kernel's 8-row sublane tiling
            raise ValueError(
                f"inference.kv_page_len must be a power of two >= 8, got "
                f"{inf.kv_page_len}")
        if inf.kv_num_pages < 0:
            raise ValueError(
                "inference.kv_num_pages must be >= 0 (0 = auto-size)")
        if inf.kv_store_pages < -1:
            raise ValueError(
                "inference.kv_store_pages must be >= -1 (0 = auto-size, "
                "-1 = no prefix store)")
        if inf.kv_page_policy not in ("uniform", "hot_bf16"):
            raise ValueError(
                f"unknown inference.kv_page_policy {inf.kv_page_policy!r} "
                "(uniform|hot_bf16)")
        if inf.kv_page_policy == "hot_bf16":
            if inf.kv_layout != "paged":
                # the policy is defined over pool pages and their refcounts;
                # a contiguous strip has neither — name the fix, like the
                # check_vma/use_cpu rejection above does
                raise ValueError(
                    "inference.kv_page_policy 'hot_bf16' requires the paged "
                    "KV layout (per-page refcounts decide which pages read "
                    "as int8); set inference.kv_layout: 'paged', or keep "
                    "kv_page_policy: 'uniform' on the contiguous layout")
            if inf.kv_cache_dtype == "int8":
                raise ValueError(
                    "inference.kv_page_policy 'hot_bf16' manages its own "
                    "int8 representation for cold pages and is mutually "
                    "exclusive with kv_cache_dtype: 'int8' (a uniformly "
                    "quantized cache has no full-precision pages to keep "
                    "hot); set kv_cache_dtype: 'auto', or keep "
                    "kv_page_policy: 'uniform' for a fully int8 cache")
        if inf.role not in ("prefill", "decode", "both"):
            raise ValueError(
                f"unknown inference.role {inf.role!r} "
                "(prefill|decode|both) — 'both' is the colocated default; "
                "'prefill'/'decode' split a disaggregated fleet")
        if inf.role != "both" and inf.kv_layout != "paged":
            raise ValueError(
                f"inference.role {inf.role!r} requires the paged KV "
                "layout (finished prefills hand off as pool pages — "
                "inference/page_transport.py); set inference.kv_layout: "
                "'paged', or keep role: 'both'")
        if not isinstance(inf.sample_on_device, bool):
            raise ValueError(
                f"inference.sample_on_device must be a JSON boolean "
                f"(true/false), got {inf.sample_on_device!r} — quoted "
                f"'true'/'false' strings are not parsed as booleans")
        if inf.attend_impl not in ATTEND_IMPLS:
            raise ValueError(
                f"unknown inference.attend_impl {inf.attend_impl!r} "
                f"({'|'.join(ATTEND_IMPLS)})")
        if inf.spec_len < 0:
            raise ValueError("inference.spec_len must be >= 0 (0 = off)")
        if inf.spec_ngram < 1:
            raise ValueError("inference.spec_ngram must be >= 1")
        if inf.drafter not in ("ngram", "learned"):
            raise ValueError(
                f"unknown inference.drafter {inf.drafter!r} (ngram|learned)"
                " — 'ngram' is the model-free prompt-lookup drafter, "
                "'learned' the EAGLE-style head over the target's last "
                "hidden state")
        if inf.spec_history_window < 0:
            raise ValueError(
                "inference.spec_history_window must be >= 0 (0 = "
                "unbounded match scan)")
        if not isinstance(inf.overlap, bool):
            raise ValueError(
                f"inference.overlap must be a JSON boolean (true/false), "
                f"got {inf.overlap!r}")
        if inf.key_schedule not in ("auto", "round", "slot"):
            raise ValueError(
                f"unknown inference.key_schedule {inf.key_schedule!r} "
                "(auto|round|slot)")
        if inf.overlap and inf.key_schedule == "round":
            raise ValueError(
                "inference.overlap requires the per-slot key schedule — "
                "round-keyed sampling ties token streams to round "
                "boundaries, which the lookahead pipeline changes; set "
                "inference.key_schedule: 'slot' (or leave it 'auto')")
        if not isinstance(inf.mixed_dispatch, bool):
            raise ValueError(
                f"inference.mixed_dispatch must be a JSON boolean "
                f"(true/false), got {inf.mixed_dispatch!r}")
        if inf.mixed_dispatch and inf.key_schedule == "round":
            raise ValueError(
                "inference.mixed_dispatch requires the per-slot key "
                "schedule — round-keyed sampling ties token streams to "
                "round boundaries, which fusing the prefill lane into "
                "decode rounds changes; set inference.key_schedule: "
                "'slot' (or leave it 'auto')")
        sc = inf.spec_controller
        if not isinstance(sc.enabled, bool):
            raise ValueError(
                f"inference.spec_controller.enabled must be a JSON "
                f"boolean, got {sc.enabled!r}")
        if sc.enabled and inf.spec_len < 1:
            raise ValueError(
                "inference.spec_controller.enabled requires "
                "inference.spec_len > 0 (spec_len is the controller's "
                "per-slot ceiling; there is no speculation to tune at 0)"
                " — set inference.spec_len, or disable the controller")
        if not 0.0 < sc.target <= 1.0:
            raise ValueError(
                "inference.spec_controller.target must be in (0, 1]")
        if not 0.0 <= sc.low <= sc.target:
            raise ValueError(
                "inference.spec_controller.low must satisfy 0 <= low <= "
                f"target (got low={sc.low}, target={sc.target}) — the "
                "[low, target) band is the hysteresis hold region")
        if sc.window < 1:
            raise ValueError("inference.spec_controller.window must be >= 1")
        if sc.hysteresis < 1:
            raise ValueError(
                "inference.spec_controller.hysteresis must be >= 1")
        if sc.cooloff < 0:
            raise ValueError(
                "inference.spec_controller.cooloff must be >= 0 rounds")
        if sc.latency_min_samples < 1:
            raise ValueError(
                "inference.spec_controller.latency_min_samples must be "
                ">= 1")
        if r.consensus_interval < 0:
            raise ValueError("consensus_interval must be >= 0 (0 = off)")
        if r.peer_timeout_s < 0:
            raise ValueError("peer_timeout_s must be >= 0 (0 = off)")
        if r.lease_interval_s <= 0:
            raise ValueError("lease_interval_s must be > 0")
        if 0 < r.peer_timeout_s <= 2 * r.lease_interval_s:
            # a timeout inside the renewal cadence would read normal lease
            # jitter as a dead host and kill healthy pods
            raise ValueError(
                f"peer_timeout_s ({r.peer_timeout_s}) must exceed "
                f"2 * lease_interval_s ({2 * r.lease_interval_s}) or be 0")
        chaos_on = False
        for name in ("chaos_raise_step", "chaos_nan_step",
                     "chaos_sigterm_step", "chaos_truncate_step"):
            v = getattr(r, name)
            if v < 0:
                raise ValueError(f"{name} must be >= 0 (0 = off)")
            chaos_on = chaos_on or v > 0
        for name in ("chaos_preempt_rank_at_step", "chaos_kill_rank_at_step",
                     "chaos_stall_rank_at_step"):
            rank, _ = parse_rank_at_step(name, getattr(r, name))
            if rank >= 0 and not self.checkpoint.save_dir:
                # a SIGKILLed/preempted pod replays the chaos step on
                # relaunch; only the fired marker persisted under save_dir
                # stops the fault re-tripping every incarnation until the
                # restart budget burns to zero
                raise ValueError(
                    f"{name} requires checkpoint.save_dir (the fired "
                    f"marker lives there; without it a supervised pod "
                    f"re-trips the fault on every relaunch)")
            chaos_on = chaos_on or rank >= 0
        if r.chaos_stall_rank_s < 0:
            raise ValueError("chaos_stall_rank_s must be >= 0")
        for name in ("chaos_dispatch_raise_round", "chaos_latency_round",
                     "chaos_poison_logits_round"):
            if getattr(r, name) < 0:
                raise ValueError(f"{name} must be >= 0 (0 = off)")
        if r.chaos_dispatch_fail_slot < -1:
            raise ValueError(
                "chaos_dispatch_fail_slot must be >= -1 (-1 = off)")
        if r.chaos_latency_s < 0:
            raise ValueError("chaos_latency_s must be >= 0")
        o = self.obs
        if o.span_ring < 1:
            raise ValueError("obs.span_ring must be >= 1")
        if o.sample_window < 1:
            raise ValueError("obs.sample_window must be >= 1")
        if o.profile_seconds <= 0:
            raise ValueError("obs.profile_seconds must be > 0")
        if chaos_on and t.steps_per_call != 1:
            # chaos fires at exact host-visible step boundaries (and NaN
            # injection swaps in a poisoned single-step program for exactly
            # one dispatch); inside a fused multi-step scan the target step
            # has no dispatch boundary of its own, so the event would
            # silently never fire — refuse instead
            raise ValueError(
                "chaos_*_step injection requires training.steps_per_call == 1")

    # ---- JSON round-trip (reference: train.py:62-63 consumes one JSON file) ----

    def to_dict(self) -> dict:
        out = dataclasses.asdict(self)
        # as it was given (0: derived), not as it reads
        out["model"]["head_dim"] = self.model._head_dim
        return out

    def to_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2)

    @classmethod
    def from_dict(cls, raw: dict[str, Any]) -> "Config":
        def build(dc, section: dict):
            known = {f.name for f in dataclasses.fields(dc)}
            return dc(**{k: v for k, v in section.items() if k in known})

        cfg = cls(
            distributed=build(DistributedConfig, raw.get("distributed", {})),
            model=build(ModelConfig, raw.get("model", {})),
            training=build(TrainingConfig, raw.get("training", {})),
            dataset=build(DatasetConfig, raw.get("dataset", {})),
            checkpoint=build(CheckpointConfig, raw.get("checkpoint", {})),
            logging=build(LoggingConfig, raw.get("logging", {})),
            resilience=build(ResilienceConfig, raw.get("resilience", {})),
            inference=build(InferenceConfig, raw.get("inference", {})),
            obs=build(ObsConfig, raw.get("obs", {})),
        )
        cfg.validate()
        return cfg

    @classmethod
    def from_json(cls, path: str) -> "Config":
        with open(path) as f:
            return cls.from_dict(json.load(f))
