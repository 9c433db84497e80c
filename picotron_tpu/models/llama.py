"""Llama-family decoder as a pure function over a parameter pytree.

Architecture spec from the reference (picotron/model.py): Embedding ->
N x DecoderLayer (RMSNorm -> Attention(+RoPE, GQA) -> residual -> RMSNorm ->
SwiGLU MLP -> residual) -> final RMSNorm -> LM head (untied, model.py:226-271).
Init laws preserved so loss curves can match: linear weights
U(-sqrt(1/fan_in), sqrt(1/fan_in)) (model.py:109-119, 172-181), embedding
N(0, 1) (model.py:220-221), norm weights ones.

Parallelism is built in rather than layered on by module surgery
(reference train.py:174-193):
- TP: weights arrive pre-sharded by shard_map; column-parallel = tp_copy + local
  matmul, row-parallel = local matmul + tp_reduce (reference
  tensor_parallel.py:35-50 module-swap table). Head counts are local,
  nh/tp and nkv/tp, as in model.py:94-97.
- CP: attention switches to ring_attention when cp_size > 1 (the reference's
  CONTEXT_PARALLEL branch, model.py:147-150); RoPE tables are sliced to the
  local chunk (model.py:201).
- PP: ``stage_apply`` is the uniform per-stage program — embedding applied on
  the first stage, loss on the last, selected by the traced 'pp' axis index
  (replacing the reference's per-stage nn.Identity surgery,
  pipeline_parallel.py:12-15).

Parameter layout: linear weights are stored (in_features, out_features) so the
forward is ``x @ w``; decoder layers are stacked on a leading layer axis and
scanned, which is also the axis pipeline parallelism shards.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name as _ckpt_name
from jax.sharding import PartitionSpec as P

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.ops.attention import sdpa
from picotron_tpu.ops.cross_entropy import (
    cross_entropy_fused,
    cross_entropy_gathered,
    cross_entropy_vocab_parallel,
)
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.rope import apply_rope, precompute_rope
from picotron_tpu.parallel.cp import ring_attention, ulysses_attention
from picotron_tpu.parallel.tp import (
    sp_gather,
    sp_scatter,
    tp_copy,
    tp_gather,
    tp_reduce,
)
from picotron_tpu.utils import (
    on_tpu,
    pvary_like,
    scan_carry_fixpoint,
    vma_checking,
)

Params = dict[str, Any]


# --------------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------------- #


def _uniform(key, shape, fan_in, dtype):
    bound = math.sqrt(1.0 / fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound).astype(dtype)


def pp_layer_layout(L: int, pp: int, interleave: int = 1):
    """Stage layer counts + stacked-row positions for the pipeline layouts.

    Even/uneven contiguous splits (interleave == 1): remainder layers go to
    the earliest stages — the reference's distribution rule
    (pipeline_parallel.py:33-36). The SPMD pipeline shards a stacked layer
    axis over 'pp', which needs equal rows per stage, so the stack is padded
    to K = ceil(L/pp) rows per stage and the pad rows are masked identity
    layers (zero weights, skipped via a validity mask — FLOP waste =
    (K*pp - L)/L, e.g. 1/32 for Llama-2-7B on pp=3).

    Interleaved (virtual-stage) layout (interleave = v > 1, requires
    L % (pp*v) == 0): the model is cut into v*pp chunks of L/(pp*v) layers;
    device s owns chunks {s, pp+s, ..., (v-1)*pp+s}, stored chunk-major in
    its contiguous K-row shard — the Megatron-style layout that lets the
    interleaved 1F1B schedule shrink the pipeline bubble by v
    (parallel/pp.py::pipeline_1f1b_interleaved).

    Returns (K, counts, positions): counts[s] = real layers on stage s,
    positions[g] = row of global layer g in the [K*pp] stacked axis.
    """
    if interleave > 1:
        assert L % (pp * interleave) == 0, (L, pp, interleave)
        Kv = L // (pp * interleave)
        K = L // pp
        positions = []
        for g in range(L):
            chunk, i = divmod(g, Kv)  # virtual stage chunk = c*pp + s
            c, s = divmod(chunk, pp)
            positions.append(s * K + c * Kv + i)
        return K, [K] * pp, positions
    base, rem = divmod(L, pp)
    counts = [base + (1 if s < rem else 0) for s in range(pp)]
    K = base + (1 if rem else 0)
    positions = []
    for s, c in enumerate(counts):
        positions += [s * K + i for i in range(c)]
    return K, counts, positions


def remap_layout(params: Params, L: int, src: tuple,
                 dst: tuple = (1, 1)) -> Params:
    """Re-arrange the stacked layer rows of ``params`` from one pipeline
    layout to another: ``src``/``dst`` are ``(pp_size, interleave)`` pairs
    as taken by ``pp_layer_layout``. Global layer g moves from row
    ``src_positions[g]`` to row ``dst_positions[g]``; rows neither layout
    uses (padding of uneven splits) are zero. The main consumer is eval on
    interleaved-trained params: ``dst=(1, 1)`` restores the contiguous
    global order ``forward_logits`` scans, without the checkpoint
    save/load round-trip previously required."""
    if tuple(src) == tuple(dst):
        return params
    _, _, pos_s = pp_layer_layout(L, *src)
    K_d, _, pos_d = pp_layer_layout(L, *dst)
    pp_d = dst[0]
    src_idx = jnp.asarray(pos_s)
    dst_idx = jnp.asarray(pos_d)

    def re(v):
        rows = v[src_idx]  # [L, ...]: real layers in global order
        if K_d * pp_d == L and pos_d == list(range(L)):
            return rows  # contiguous unpadded target: pure permutation
        out = jnp.zeros((K_d * pp_d,) + v.shape[1:], v.dtype)
        return out.at[dst_idx].set(rows)

    return {**params, "layers": jax.tree.map(re, params["layers"])}


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> Params:
    """Global (unsharded-shape) parameter pytree. Jit with out_shardings to
    materialize directly as sharded arrays — replaces the reference's
    meta-device init + materialization dance (checkpoint.py:15-48, 50-102).

    Real-layer weights are drawn with an [L, ...] leading axis regardless of
    ``pp_size``/``interleave``, then scattered into the stacked-row layout
    (padded for uneven splits, chunk-permuted for interleaved 1F1B) — so the
    model function is identical across topologies and the equivalence oracle
    holds for every layout."""
    H, I, V, L = m.hidden_size, m.intermediate_size, m.vocab_size, m.num_hidden_layers
    D = m.head_dim
    Hq, Hkv = m.num_attention_heads * D, m.num_key_value_heads * D
    dt = jnp.dtype(m.dtype)
    ks = {name: jax.random.fold_in(key, i) for i, name in enumerate(
        ["embed", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "lm_head"])}
    ones = lambda *shape: jnp.ones(shape, dt)
    layers = {
        "attn_norm": ones(L, H),
        "wq": _uniform(ks["wq"], (L, H, Hq), H, dt),
        "wk": _uniform(ks["wk"], (L, H, Hkv), H, dt),
        "wv": _uniform(ks["wv"], (L, H, Hkv), H, dt),
        "wo": _uniform(ks["wo"], (L, Hq, H), Hq, dt),
        "mlp_norm": ones(L, H),
        "w_gate": _uniform(ks["w_gate"], (L, H, I), H, dt),
        "w_up": _uniform(ks["w_up"], (L, H, I), H, dt),
        "w_down": _uniform(ks["w_down"], (L, I, H), I, dt),
    }
    if L % pp_size != 0 or interleave > 1:
        K, _, positions = pp_layer_layout(L, pp_size, interleave)
        idx = jnp.asarray(positions)
        layers = {
            k: jnp.zeros((K * pp_size,) + v.shape[1:], v.dtype).at[idx].set(v)
            for k, v in layers.items()
        }
    return {
        "embed": jax.random.normal(ks["embed"], (V, H), jnp.float32).astype(dt),
        "layers": layers,
        "final_norm": ones(H),
        "lm_head": _uniform(ks["lm_head"], (H, V), H, dt),
    }


# The matmul weights eligible for per-channel int8 quantization
# (inference.weight_dtype: "int8"): the seven decoder-layer projections
# plus the LM head ("lm_head" at the tree top). Embedding and norms stay
# full precision — they are tiny next to the stack and their error
# characteristics differ (the embedding is a gather, not a matmul).
QUANT_WEIGHT_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def matmul(x, w):
    """``x @ w`` dispatching on the weight leaf's form: a plain array runs
    the dense matmul; a quantized ``{"q": int8, "s": fp32}`` pair (see
    ops/pallas/quant_matmul.py) runs the fused dequant matmul — the
    Pallas kernel on TPU, the XLA int8-einsum fallback elsewhere; an
    adapter-wrapped ``{"w", "a", "b", "ids"}`` leaf (multi-tenant
    serving, ops/pallas/lora_matmul.py) recurses on its base ``w`` —
    which may itself be the quantized pair — and adds the per-row
    segmented LoRA residual on top, so one dispatch mixes tenants while
    the base weights stay int8 or bf16 untouched. A trace-time Python
    branch, exactly like the attend_impl dispatch: each leaf form traces
    its own program, no runtime cost. Output dtype follows ``x`` on the
    quantized path (the dense path's promotion rule for same-dtype
    operands) and the base output on the adapter path (the fp32 residual
    casts onto it)."""
    from picotron_tpu.ops.pallas.lora_matmul import (
        is_lora_weight,
        lora_matmul,
    )
    from picotron_tpu.ops.pallas.quant_matmul import (
        is_quant_weight,
        quant_matmul,
    )

    if is_lora_weight(w):
        base = matmul(x, w["w"])
        return base + lora_matmul(x, w["a"], w["b"],
                                  w["ids"]).astype(base.dtype)
    if is_quant_weight(w):
        return quant_matmul(x, w["q"], w["s"])
    return x @ w


def quantize_params(params: Params) -> Params:
    """Quantize every eligible matmul weight (QUANT_WEIGHT_LEAVES +
    lm_head) to per-output-channel int8 pairs; embedding/norms pass
    through untouched. The stacked layer axis rides along (scales come
    out [L, out] — one scale vector per layer per leaf). The in-memory
    counterpart of checkpoint.load_* with ``weight_dtype="int8"`` (used
    by the random-init serving path and tests).

    Deliberately EAGER, leaf by leaf — op-by-op dispatch keeps scales
    bit-identical across every quantization path (this, the host numpy
    streamer, a restored sharded tree; a jitted variant drifts a ulp
    when XLA rewrites the /127), transients are bounded to one leaf's
    fp32 copy (sharded when the leaf is — restore against sharded
    ShapeDtypeStructs so a 7B tree never concentrates on one device),
    and each dense leaf frees as soon as the caller drops its tree."""
    from picotron_tpu.ops.pallas.quant_matmul import quantize_weight

    layers = {k: (quantize_weight(v) if k in QUANT_WEIGHT_LEAVES else v)
              for k, v in params["layers"].items()}
    return {**params, "layers": layers,
            "lm_head": quantize_weight(params["lm_head"])}


def dequantize_params(params: Params, dtype) -> Params:
    """The fake-quant reference tree: every quantized leaf dequantized
    back to ``dtype``. TESTS ONLY — a dense engine fed this tree is the
    oracle the int8 engine's generations are pinned against (the
    quantization error is in both; only the fused-matmul plumbing
    differs)."""
    from picotron_tpu.ops.pallas.quant_matmul import (
        dequantize_weight,
        is_quant_weight,
    )

    def deq(leaf):
        if is_quant_weight(leaf):
            return dequantize_weight(leaf["q"], leaf["s"], dtype)
        return leaf

    layers = {k: deq(v) for k, v in params["layers"].items()}
    return {**params, "layers": layers, "lm_head": deq(params["lm_head"])}


def param_bytes(params: Params) -> int:
    """Total bytes the parameter tree occupies (int8 values + fp32
    scales included) — the ``weight_bytes_total`` metric the int8 mode
    roughly halves (kv_cache.cache_bytes' weight-side twin)."""
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))


# FSDP: the axis (AFTER the scan slices off the leading layer-stack axis)
# each layer param rests dp-sharded on and is all-gathered over just in
# time inside decoder_layer. Every entry is an H-sized axis, so the single
# divisibility constraint is hidden_size % dp == 0 (config validation).
FSDP_GATHER_AXIS = {
    "attn_norm": 0, "wq": 0, "wk": 0, "wv": 0, "wo": 1,
    "mlp_norm": 0, "w_gate": 0, "w_up": 0, "w_down": 1,
}


def param_pspecs(_: ModelConfig, fsdp: bool = False,
                 weight_dtype: str = "bf16") -> Params:
    """PartitionSpecs: layer stack sharded over 'pp' (contiguous stage slices,
    the rule at reference pipeline_parallel.py:33-36), column-parallel weights
    shard out-features over 'tp', row-parallel shard in-features, embedding is
    vocab-parallel (reference tensor_parallel.py:35-50); embed/final_norm/
    lm_head are replicated across 'pp' stages. Everything replicated over
    'dp' and 'cp' — except with ``fsdp``, where each LAYER param additionally
    rests dp-sharded on its H-sized axis (FSDP_GATHER_AXIS) and is gathered
    just in time in decoder_layer.

    ``weight_dtype="int8"`` mirrors the quantized tree's shape: every
    eligible matmul leaf becomes a ``{"q", "s"}`` pair whose int8 values
    keep the dense spec and whose per-output-channel scales drop the
    contraction axis — scales shard WITH their channels (a tp-sharded
    column split carries its own channels' scales, replicated nowhere)."""
    layers = {
        "attn_norm": P("pp", None),
        "wq": P("pp", None, "tp"),
        "wk": P("pp", None, "tp"),
        "wv": P("pp", None, "tp"),
        "wo": P("pp", "tp", None),
        "mlp_norm": P("pp", None),
        "w_gate": P("pp", None, "tp"),
        "w_up": P("pp", None, "tp"),
        "w_down": P("pp", "tp", None),
    }
    if fsdp:
        if weight_dtype == "int8":
            # FSDP is a training rewrite; quantized weights are a serving
            # format (inference_config turns fsdp off) — reject the combo
            # rather than invent gather semantics for scale leaves
            raise ValueError(
                "fsdp and int8 weight quantization are mutually exclusive "
                "(quantized weights serve; FSDP trains)")
        for name, ax in FSDP_GATHER_AXIS.items():
            spec = list(layers[name])
            assert spec[ax + 1] is None, (name, spec)  # +1: stack axis
            spec[ax + 1] = "dp"
            layers[name] = P(*spec)
    specs = {
        "embed": P("tp", None),
        "layers": layers,
        "final_norm": P(),
        "lm_head": P(None, "tp"),
    }
    if weight_dtype == "int8":
        def qspec(spec):
            t = tuple(spec)
            return {"q": spec, "s": P(*t[:-2], t[-1])}

        specs["layers"] = {
            k: (qspec(v) if k in QUANT_WEIGHT_LEAVES else v)
            for k, v in layers.items()
        }
        specs["lm_head"] = qspec(specs["lm_head"])
    return specs


# Multi-tenant adapters: which projections contract over a tp-sharded
# axis (row-parallel) — their adapter A shards WITH the contraction so
# the residual's partial sums ride the same tp_reduce the base output
# does; everywhere else A replicates and B shards its out-features.
_ROW_PARALLEL = ("wo", "w_down")


def adapter_pspecs(specs: Params) -> Params:
    """Wrap a ``param_pspecs`` tree's seven projection leaves into the
    adapter leaf form ``{"w": base_spec, "a", "b", "ids"}`` (see
    ops/pallas/lora_matmul.py). a is [L, T, in, r] sharded 'pp' on the
    stack and — row-parallel leaves only — 'tp' on the contraction;
    b is [L, T, r, out] sharded 'pp' + 'tp' on out-features for
    column-parallel leaves; ids is the [L, B] per-row adapter-id
    broadcast, 'pp'-sharded with the stack. The base leaf spec (dense
    or quantized pair) nests untouched, so adapter engines shard their
    base weights exactly like non-adapter engines do."""
    layers = dict(specs["layers"])
    for name in QUANT_WEIGHT_LEAVES:
        row = name in _ROW_PARALLEL
        layers[name] = {
            "w": layers[name],
            "a": P("pp", None, "tp" if row else None, None),
            "b": P("pp", None, None, None if row else "tp"),
            "ids": P("pp", None),
        }
    return {**specs, "layers": layers}


def bind_adapters(params: Params, pack_leaves: dict, ids) -> Params:
    """Wrap the seven projection leaves with the adapter pack + this
    dispatch's per-row adapter ids (``ids`` [B] int32) — the host-side
    step before every adapter-engine dispatch. ``pack_leaves`` is
    AdapterPack.device_leaves(): ``{leaf: {"a": [L, T, in, R],
    "b": [L, T, R, out]}}``. ids broadcasts to [L, B] so the layer scan
    slices a per-layer [B] row alongside each weight. Cheap: a dict
    rebuild around existing device arrays plus one tiny broadcast."""
    from picotron_tpu.ops.pallas.lora_matmul import is_lora_weight

    if is_lora_weight(params["layers"]["wq"]):
        raise ValueError("params are already adapter-bound — bind once "
                         "per dispatch from the BASE tree")
    ids = jnp.asarray(ids, jnp.int32).reshape(-1)
    L = params["layers"]["attn_norm"].shape[0]
    ids_l = jnp.broadcast_to(ids[None, :], (L, ids.shape[0]))
    layers = dict(params["layers"])
    for name in QUANT_WEIGHT_LEAVES:
        layers[name] = {"w": layers[name], "a": pack_leaves[name]["a"],
                        "b": pack_leaves[name]["b"], "ids": ids_l}
    return {**params, "layers": layers}


def merge_adapter(params: Params, leaves: dict) -> Params:
    """The merged-weight reference tree ``W + A @ B`` — TESTS AND PARITY
    TOOLING ONLY (generate.py --check-adapter-parity): a dense engine
    fed this tree is the solo-tenant oracle the segmented multi-tenant
    dispatch's generations are pinned against. ``leaves`` maps leaf
    name -> (a [L, in, r], b [L, r, out]) (AdapterPack.random_leaves
    format). Dense trees only — an int8 engine's oracle merges into its
    fake-quant dense twin (llama.dequantize_params), mirroring the
    weight-parity gate."""
    from picotron_tpu.ops.pallas.quant_matmul import is_quant_weight

    layers = dict(params["layers"])
    for name, (a, b) in leaves.items():
        w = layers[name]
        if is_quant_weight(w):
            raise ValueError(
                f"merge_adapter needs dense weights; {name} is quantized "
                f"— dequantize_params first (the weight-parity recipe)")
        delta = jnp.einsum("lkr,lrn->lkn", jnp.asarray(a, jnp.float32),
                           jnp.asarray(b, jnp.float32),
                           preferred_element_type=jnp.float32)
        layers[name] = (w.astype(jnp.float32) + delta).astype(w.dtype)
    return {**params, "layers": layers}


# --------------------------------------------------------------------------- #
# forward pieces (all run inside shard_map; collectives over size-1 axes are free)
# --------------------------------------------------------------------------- #


def use_sp(cfg: Config) -> bool:
    """Sequence parallelism is active (a no-op rewrite at tp == 1)."""
    return cfg.distributed.tp_sequence_parallel and cfg.distributed.tp_size > 1


def embed_lookup(w, tokens, sp: bool = False, cfg=None):
    """Vocab-parallel embedding: mask out-of-shard tokens, psum partials
    (reference VocabParallelEmbedding, tensor_parallel.py:246-271). With
    sequence parallelism the partial sums are reduce-scattered straight to
    this rank's seq shard instead of fully reduced. ``cfg`` is the serving
    seam's (``models/__init__.py``); this block reads nothing of it."""
    v_local = w.shape[0]
    start = lax.axis_index("tp") * v_local
    local = tokens - start
    ok = (local >= 0) & (local < v_local)
    e = jnp.take(w, jnp.clip(local, 0, v_local - 1), axis=0)
    e = e * ok[..., None].astype(w.dtype)
    return sp_scatter(e) if sp else tp_reduce(e)


def _attention_impl(cfg: Config) -> str:
    """``model.attention_impl`` with "auto" resolved: the flash kernels on a
    TPU, ``sdpa`` elsewhere."""
    impl = cfg.model.attention_impl
    if impl == "auto":
        impl = "flash" if on_tpu() else "sdpa"
    return impl


def flash_heads_per_row(cfg: Config) -> int:
    """Heads the training layer stack's flash kernels take to a 128-lane
    row: 2 where heads of 64 go through them two by two, as the projections
    and RoPE leave them (the ``paired`` form of
    ops/pallas/flash_attention.py: no relayout copy around a call), 1 where
    a call folds its operands to a head a row. Of the configuration alone:
    half-lane heads, an even number of them on a tp rank, no context
    parallelism, ``flash_layout`` left at its default, the flash kernels in
    use. A prefill keeps a head a row whatever this says (``_attention``):
    it builds one program a bucket and runs each a handful of times."""
    from picotron_tpu.ops.pallas.flash_attention import LANE

    m, d = cfg.model, cfg.distributed
    paired = (_attention_impl(cfg) == "flash" and 2 * m.head_dim == LANE
              and (m.num_attention_heads // d.tp_size) % 2 == 0
              and d.cp_size == 1 and m.flash_layout == "folded")
    return 2 if paired else 1


def _attention(q, k, v, cfg: Config, cache=None, pos=None, layer=None,
               paired: bool = False):
    """Full-sequence attention (training / prefill), or — when ``cache`` is
    given — the incremental decode path: ``cache`` is the UPDATED stacked
    cache dict (``{"k","v"[, "k_scale","v_scale"]}``, each
    [L, B, max_len, n_kv_local, ...] with compact GQA heads, never
    repeated), ``layer`` the index of the layer to read and ``pos`` [B] the
    first index just written per sequence; the ``k``/``v`` positional args
    are ignored. ``kv_cache.attend`` reads the layer where it lies: a
    masked dot product over its block, or on a TPU the flash-decode kernel
    over the stacked leaf for the plain decode step. ``paired``: the
    training stack's call where ``flash_heads_per_row`` says 2.
    """
    scale = 1.0 / math.sqrt(cfg.model.head_dim)
    if cache is not None:
        from picotron_tpu.inference.kv_cache import attend

        # S queries starting at per-sequence write index ``pos``: the valid
        # key count is pos + S (S == 1 decode, S > 1 chunked prefill or
        # speculative verify). ``inference.attend_impl`` picks the kernel:
        # "auto" (on a TPU the flash-decode kernel for the plain decode
        # step, dense elsewhere), the dense whole-window reference, or the
        # length-aware Pallas kernels everywhere (which read int8 blocks as
        # stored; the dense path dequantizes whole blocks on the fly). The
        # impl string is a Python value, so each choice traces its own
        # program under jit.
        return attend(q, cache, pos + q.shape[1], scale,
                      impl=cfg.inference.attend_impl, layer=layer)
    impl = _attention_impl(cfg)
    if cfg.distributed.cp_size > 1:
        if cfg.distributed.cp_impl == "ulysses":
            # all-to-all seq<->head reshard around one full-sequence kernel
            return ulysses_attention(q, k, v, scale, "cp",
                                     cfg.distributed.cp_size, True,
                                     impl == "flash",
                                     cfg.model.flash_block_q,
                                     cfg.model.flash_block_k,
                                     cfg.model.flash_layout)
        # ring with Pallas flash blocks on TPU, XLA einsum blocks elsewhere
        return ring_attention(q, k, v, scale, "cp", cfg.distributed.cp_size,
                              True, impl == "flash",
                              cfg.distributed.cp_zigzag,
                              cfg.model.flash_block_q,
                              cfg.model.flash_block_k,
                              cfg.model.flash_layout)
    if impl == "flash":
        from picotron_tpu.ops.pallas.flash_attention import flash_attention

        return flash_attention(q, k, v, scale, causal=True,
                               block_q=cfg.model.flash_block_q,
                               block_k=cfg.model.flash_block_k,
                               layout="paired" if paired
                               else cfg.model.flash_layout)
    return sdpa(q, k, v, scale, causal=True)


def _norm(x, w, cfg: Config):
    use_pallas = cfg.model.use_pallas_rmsnorm
    if use_pallas is None:
        use_pallas = on_tpu()
    if use_pallas:
        from picotron_tpu.ops.pallas.rmsnorm import rms_norm_pallas

        return rms_norm_pallas(x, w, cfg.model.rms_norm_eps)
    return rms_norm(x, w, cfg.model.rms_norm_eps)


def decoder_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                  return_kv: bool = False, layer=None):
    """One decoder block with per-shard head counts (model.py:94-97,187-208).

    With sequence parallelism the residual stream ``h`` is seq-sharded over
    'tp': the norm runs on the local shard, the Megatron f/g collectives
    become all-gather (entering column-parallel) / reduce-scatter (leaving
    row-parallel), and attention/MLP still see the full (cp-local) sequence.

    Inference hooks (picotron_tpu/inference/):
    - ``return_kv=True`` (prefill): the full-sequence path runs unchanged
      but the layer also returns its compact pre-repeat rotated K/V block
      [B, S, n_kv_local, head_dim] for the caller to park in a KV cache —
      return value becomes ``(h, (k, v))``.
    - ``cache={"k","v"[,"k_scale","v_scale"]}`` + ``layer`` + ``pos`` [B]
      (decode / chunked prefill / speculative verify): ``cache`` holds the
      STACKED [L, ...] leaves the engine's layer scan carries and ``layer``
      is this layer's index into them. The new tokens' K/V rows are written in place at
      ``[layer, slot, pos..]`` (int8 caches quantize on write —
      kv_cache.cache_write) and attention runs as a masked dot product
      over that layer of the cache, read through the index
      (``_attention``'s decode path); ``cos``/``sin`` must then be the
      per-sequence [B, S, head_dim] tables from
      ``ops.rope.rope_at_positions``. S == 1 is the per-slot decode step;
      S > 1 with B == 1 is a single-slot prefill chunk; S > 1 with B > 1
      is the multi-token decode hook — EVERY slot scores S contiguous
      positions from its own offset in one pass (speculative decoding's
      verify dispatch, engine._verify_impl). Return value is
      ``(h, updated_cache_dict)``. All assume cp == 1 (the serving mesh
      is tp-only; inference/engine.py enforces it)."""
    m, tp = cfg.model, cfg.distributed.tp_size
    nh, nkv, D = m.num_attention_heads // tp, m.num_key_value_heads // tp, m.head_dim
    sp = use_sp(cfg)
    enter = sp_gather if sp else tp_copy
    leave = sp_scatter if sp else tp_reduce

    if cfg.distributed.fsdp:
        # FSDP just-in-time materialization: gather each dp-sharded layer
        # param for this layer only; the gather's AD transpose
        # reduce-scatters (dp-sums) the grads back onto the shards. Free
        # at dp == 1. The "peak = one layer's full params" property needs
        # a remat mode that RECOMPUTES the gather in backward (any mode
        # but "none"); under remat="none" the gathered params are saved
        # as AD residuals across the whole stack, keeping only the
        # grad/optimizer-state 1/dp savings.
        lp = {k: lax.all_gather(v, "dp", axis=FSDP_GATHER_AXIS[k],
                                tiled=True)
              for k, v in lp.items()}

    # attention sub-block: column(q,k,v) -> rope -> attn -> row(out)
    # (checkpoint_name tags are inert outside jax.checkpoint policies;
    # remat="save_attn" keeps flash_out/lse, remat="offload" parks every
    # tagged residual in pinned host memory — layers_forward docstring)
    x = _ckpt_name(enter(_norm(h, lp["attn_norm"], cfg)), "attn_in")
    B, S, _ = x.shape
    # the training stack's call at heads of 64: its flash kernels take the
    # rows as the projections write them, two heads to 128 lanes, so RoPE
    # stays in rows too. A prefill (one program a bucket, each run a handful
    # of times) and every cache path keep a head a row, and the program they
    # lowered to before.
    paired = (cache is None and not return_kv and nkv == nh
              and flash_heads_per_row(cfg) == 2)
    if paired:
        from picotron_tpu.ops.pallas.rope import rope_rows

        q, k = (r.reshape(B, S, nh, D) for r in rope_rows(
            matmul(x, lp["wq"]), matmul(x, lp["wk"]), cos, sin))
    else:
        q = matmul(x, lp["wq"]).reshape(B, S, nh, D)
        k = matmul(x, lp["wk"]).reshape(B, S, nkv, D)
    v = _ckpt_name(matmul(x, lp["wv"]).reshape(B, S, nkv, D), "v_proj")
    if not paired:
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    q, k = _ckpt_name(q, "q_rope"), _ckpt_name(k, "k_rope")

    new_cache = None
    if cache is not None:
        # incremental decode (S == 1, one row per slot), chunked prefill
        # (S > 1, one slot's contiguous block), or speculative verify
        # (S > 1, every slot's contiguous block): write the fresh K/V at
        # each sequence's position (quantizing for int8 caches), attend
        # over this layer of the cache
        from picotron_tpu.inference.kv_cache import cache_write

        new_cache = cache_write(cache, k, v, pos, layer)
        o = _attention(q, None, None, cfg, cache=new_cache, pos=pos,
                       layer=layer)
    else:
        kv_compact = (k, v)  # pre-repeat: what a prefill parks in the cache
        cp, cp_impl = cfg.distributed.cp_size, cfg.distributed.cp_impl
        # GQA + context parallelism: the compact Hkv-head K/V ride the wire
        # (Hq/Hkv x less ICI traffic than the reference's pre-repeat,
        # model.py:141-142) whenever the CP algorithm supports it — always
        # for the ring (expand per block), for Ulysses when the local kv
        # heads split evenly over cp (expand after the all-to-all).
        compact_cp = cp > 1 and (cp_impl == "ring" or nkv % cp == 0)
        if nkv != nh and not compact_cp:
            k = jnp.repeat(k, nh // nkv, axis=2)
            v = jnp.repeat(v, nh // nkv, axis=2)
        o = _attention(q, k, v, cfg, paired=paired)
    o = o.reshape(B, S, nh * D)
    h = h + leave(matmul(o, lp["wo"]))

    # MLP sub-block: column(gate,up) -> SwiGLU -> row(down)  (model.py:163-185)
    x = _ckpt_name(enter(_norm(h, lp["mlp_norm"], cfg)), "mlp_in")
    g = _ckpt_name(matmul(x, lp["w_gate"]), "mlp_gate")
    u = _ckpt_name(matmul(x, lp["w_up"]), "mlp_up")
    y = _ckpt_name(jax.nn.silu(g) * u, "mlp_act")
    out = h + leave(matmul(y, lp["w_down"]))
    if new_cache is not None:
        return out, new_cache
    return (out, kv_compact) if return_kv else out


def layer_valid_mask(stacked, cfg: Config):
    """Validity mask for the scanned layer rows, or None when every row is a
    real layer (even split). Two cases for uneven splits:
    - rows == K (a stage's local slice inside the pipeline): row i is real
      iff i < counts[stage], with the stage from ``lax.axis_index('pp')``;
    - rows == K*pp (the full padded stack — eval paths like forward_logits
      running on a mesh that holds the whole stack): position p is real iff
      (p % K) < counts[p // K]."""
    L, pp = cfg.model.num_hidden_layers, cfg.distributed.pp_size
    if L % pp == 0:
        return None
    K, counts, _ = pp_layer_layout(L, pp)
    rows = jax.tree.leaves(stacked)[0].shape[0]
    if rows == K * pp:
        return jnp.asarray([(p % K) < counts[p // K] for p in range(rows)])
    base, rem = divmod(L, pp)
    n_s = base + (lax.axis_index("pp") < rem)
    return jnp.arange(K) < n_s


# every residual decoder_layer tags with checkpoint_name, in forward
# order — the remat="offload" policy parks these in pinned host memory
OFFLOAD_NAMES = ("attn_in", "q_rope", "k_rope", "v_proj", "flash_out",
                 "flash_lse", "mlp_in", "mlp_gate", "mlp_up", "mlp_act")


def layers_forward(stacked, h, cos, sin, cfg: Config):
    """Scan over the locally-held layer stack (this stage's contiguous slice).
    Pad rows of an uneven pipeline split are skipped via the validity mask
    (h passes through unchanged, so their weights get zero gradients).

    remat modes (training.remat):
    - "none": save every intermediate (XLA default) — fastest, most memory;
    - "full": jax.checkpoint per layer — recompute the whole layer forward
      during backward, save only layer-boundary activations;
    - "save_attn": per-layer checkpoint with a policy that keeps the flash-
      attention output + LSE (named inside the kernel's VJP,
      ops/pallas/flash_attention.py) — the backward recomputes the cheap
      norm/matmul chain but never re-runs the flash forward kernel, for
      ~(S*H + S) extra bf16/fp32 floats per layer;
    - "offload": every tagged residual (attn_in/q_rope/k_rope/v_proj/
      flash_out/flash_lse/mlp_in/mlp_gate/mlp_up/mlp_act — decoder_layer)
      is parked in pinned HOST memory during forward and streamed back for
      backward: near-zero recompute at near-zero HBM, paid for in
      host-link bandwidth. Pays only when the host link sustains
      ~bytes/FLOP of the model: ≈ (12H + 6I) bytes per token-layer
      against 2(4H^2 + 3HI) FLOPs — a crossover around H ~ 14k at an
      assumed 16 GB/s link, inversely proportional to the measured
      bandwidth (tools/measure_offload_bw). The mode exists for the
      big-model pod regime; no benchmark cell uses it."""
    valid = layer_valid_mask(stacked, cfg)

    if valid is None:
        def body(h, lp):
            return decoder_layer(lp, h, cos, sin, cfg), None
        xs = stacked
    else:
        def body(h, xs):
            lp, v = xs
            return jnp.where(v, decoder_layer(lp, h, cos, sin, cfg), h), None
        xs = (stacked, valid)

    remat = cfg.training.remat
    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "save_attn":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                "flash_out", "flash_lse"))
    elif remat == "offload":
        body = jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=list(OFFLOAD_NAMES),
                offload_src="device", offload_dst="pinned_host"))
    if vma_checking("pp"):
        h = scan_carry_fixpoint(body, h, jax.tree.map(lambda a: a[0], xs))
    h, _ = lax.scan(body, h, xs)
    return h


# What the serving stack takes from a model module besides the functions
# above (models/__init__.py): the groups of stacked layers it scans, the
# angle tables of a cache window, the contiguous cache, and the names of the
# counters a layer returns.
STAT_NAMES = ()
UNSLICED = ()  # every leaf of a layer is sliced out of its stack by the scan


def layer_groups(m: ModelConfig) -> list:
    return [("layers", decoder_layer, m.num_hidden_layers)]


def serving_rope_tables(m: ModelConfig, seq_len: int, dtype) -> tuple:
    return precompute_rope(seq_len, m.head_dim, m.rope_theta, dtype)


def cache_pspecs(m: ModelConfig, quantized: bool = False,
                 dp: int = 1) -> dict:
    from picotron_tpu.inference import kv_cache

    return kv_cache.cache_pspecs(quantized, dp=dp)


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1) -> dict:
    from picotron_tpu.inference import kv_cache

    return kv_cache.init_cache(m, slots, max_seq_len, dtype=dtype,
                               quantized=quantized, tp=tp)


def _head_input(params, h, cfg: Config):
    """Final norm + tp copy — the shared prefix of logits and loss paths.
    With sequence parallelism the norm runs on the local seq shard and the
    result is all-gathered to the full sequence for the vocab-sharded head."""
    x = _norm(h, params["final_norm"], cfg)
    return sp_gather(x) if use_sp(cfg) else tp_copy(x)


def head_logits(params, h, cfg: Config):
    """Final norm + untied LM head (the reference always creates a fresh
    untied head, checkpoint.py:88-91); logits stay vocab-sharded. The
    head matmul dispatches on the leaf form, so an int8-quantized head
    serves through the same fused dequant matmul as the layer stack."""
    return matmul(_head_input(params, h, cfg), params["lm_head"])


def loss_from_hidden(params, h, targets, cfg: Config):
    """Final norm -> LM head -> mean CE, by the configured loss_impl:
    - "fused" (default): row-chunked fused linear+CE — full fp32 logits are
      never materialized (ops/cross_entropy.py:cross_entropy_fused);
    - "gathered": reference-parity path — logits gathered over 'tp' then
      plain CE (tensor_parallel.py:48-50, train.py:46-49);
    - "vocab_parallel": materialized local logits, psum'd CE statistics."""
    impl = cfg.model.loss_impl
    if impl == "auto":
        impl = "fused"
    x = _head_input(params, h, cfg)
    if impl == "fused":
        return cross_entropy_fused(x, params["lm_head"], targets)
    logits = x @ params["lm_head"]
    if impl == "gathered":
        return cross_entropy_gathered(logits, targets)
    return cross_entropy_vocab_parallel(logits, targets)


def rope_tables(cfg: Config):
    """Full-sequence tables; sliced per cp rank inside the step."""
    return precompute_rope(
        cfg.training.seq_length, cfg.model.head_dim, cfg.model.rope_theta,
        jnp.dtype(cfg.model.dtype))


def slice_rope_for_cp(cos, sin, s_local, cfg: Config):
    """Each cp rank's rows of the angle tables, matching its token positions
    (reference model.py:201, context_parallel.py:189-195). Zigzag ranks own
    two non-adjacent chunks -> two dynamic slices."""
    rank = lax.axis_index("cp")
    if cfg.distributed.cp_zigzag and cfg.distributed.cp_size > 1:
        n = cfg.distributed.cp_size
        h = s_local // 2
        early = rank * h
        late = (2 * n - 1 - rank) * h

        def take(t):
            return jnp.concatenate(
                [lax.dynamic_slice_in_dim(t, early, h, 0),
                 lax.dynamic_slice_in_dim(t, late, h, 0)], axis=0)

        return take(cos), take(sin)
    start = rank * s_local
    return (lax.dynamic_slice_in_dim(cos, start, s_local, 0),
            lax.dynamic_slice_in_dim(sin, start, s_local, 0))


def _stage_gating(cfg: Config) -> bool:
    """Whether per-stage embed/loss gating uses ``lax.cond`` (true branch
    executed only on the owning stage) or a compute-both ``jnp.where`` mask.

    On TPU, collectives inside a cond taken by a subset of devices are safe
    as long as every replica group is entirely inside or outside the branch —
    true here, since the predicate depends only on the 'pp' index and the
    gated collectives reduce over 'tp'. The XLA *CPU* runtime's in-process
    rendezvous, however, intermittently aborts when a collective op is
    reached by a subset of devices, so the CPU test/dryrun path defaults to
    masking with ``where`` instead (the pre-gating semantics; the FLOP waste
    only matters on real chips).

    ``distributed.stage_gating`` overrides the default ("cond"/"where"):
    forcing "cond" on a CPU mesh lets the equivalence suite run the exact
    gated program a TPU pod executes — safe when the gated branches carry
    no collectives (tp=1 pipelines)."""
    mode = cfg.distributed.stage_gating
    if mode == "cond":
        return True
    if mode == "where":
        return False
    # "auto": a config that REQUESTS the CPU mesh (use_cpu) resolves to
    # where-masking regardless of what the default backend happens to be —
    # on_tpu() sniffs the process-global backend, which on a TPU host would
    # otherwise cond-gate a run that is actually executing on host devices
    # (and config.validate's check_vma guard predicts resolution from
    # use_cpu, so this keeps validation and resolution aligned).
    if cfg.distributed.use_cpu:
        return False
    return on_tpu()


def _stage_input(params, h_recv, tokens, cfg: Config, is_first=None):
    """Stage input: the embedding on the first (virtual) stage, the received
    activation elsewhere — gated so non-first stages never pay the
    vocab-parallel embedding lookup (the reference instantiates the
    embedding only on stage 0, pipeline_parallel.py:12-15). ``is_first``
    overrides the default first-stage predicate (the interleaved engine
    passes "device 0 AND chunk 0")."""
    dt = jnp.dtype(cfg.model.dtype)
    sp = use_sp(cfg)
    if cfg.distributed.pp_size == 1:
        return embed_lookup(params["embed"], tokens, sp).astype(dt)
    pred = (lax.axis_index("pp") == 0) if is_first is None else is_first
    if _stage_gating(cfg):
        # no vma casts here: cond gating + check_vma is rejected at config
        # validation (the checker's auto-inserted pvary transposes put real
        # psums inside single-stage branches), so this path never runs
        # under the checker
        return lax.cond(
            pred,
            lambda: embed_lookup(params["embed"], tokens, sp).astype(dt),
            lambda: h_recv,
        )
    emb = embed_lookup(params["embed"], tokens, sp).astype(dt)
    return jnp.where(pred, emb, h_recv)


def _stage_loss(params, h, targets, cfg: Config, is_last=None):
    """Loss, computed only on the last (virtual) stage (reference
    pipeline_parallel.py:67-69, 97-100) — gated so earlier stages skip the
    LM-head matmul (for SmolLM a 2048x49152 matmul, ~10% of model FLOPs).
    ``is_last`` overrides the default last-stage predicate (the interleaved
    engine passes "device pp-1 AND chunk v-1")."""
    pp = cfg.distributed.pp_size
    if pp == 1:
        return loss_from_hidden(params, h, targets, cfg)
    pred = (lax.axis_index("pp") == pp - 1) if is_last is None else is_last
    if _stage_gating(cfg):
        # cond gating + check_vma is rejected at validation; no casts here
        return lax.cond(
            pred,
            lambda: loss_from_hidden(params, h, targets, cfg),
            lambda: jnp.zeros((), jnp.float32),
        )
    loss = loss_from_hidden(params, h, targets, cfg)
    return jnp.where(pred, loss, 0.0)


def stage_apply(params, h_recv, tokens, targets, cos, sin, cfg: Config,
                is_first=None, is_last=None):
    """The uniform per-pipeline-stage program. Returns (h_out, loss) where
    h_out is the activation sent downstream (pre-final-norm) and loss is
    nonzero only on the last stage. Embedding and LM-head/loss are cond-gated
    to their owning (virtual) stages, so no stage wastes the other stages'
    FLOPs."""
    h = _stage_input(params, h_recv, tokens, cfg, is_first)
    s_local = tokens.shape[-1]
    cos_l, sin_l = slice_rope_for_cp(cos, sin, s_local, cfg)
    h = layers_forward(params["layers"], h, cos_l, sin_l, cfg)
    loss = _stage_loss(params, h, targets, cfg, is_last)
    return h, loss


def stage_fwd_save(params, h_recv, tokens, targets, cos, sin, cfg: Config,
                   is_first=None, is_last=None):
    """Forward for the manual-backward 1F1B engine: ``stage_apply`` that also
    returns the activations ``stage_bwd`` needs — the input to every local
    layer plus the final hidden state. This is the layer-granular
    checkpointing set, so a stage's in-flight memory is L_local + 1 boundary
    tensors per microbatch, never the full per-layer intermediates the
    reference's no-remat 1F1B holds
    (pipeline_parallel.py:46-52). Note the 1F1B engine is layer-remat *by
    construction*: ``training.remat`` governs the AD engines (afab /
    no_pipeline); here the backward always re-derives each layer's VJP from
    its boundary (docs/PP_COST.md)."""
    h = _stage_input(params, h_recv, tokens, cfg, is_first)
    s_local = tokens.shape[-1]
    cos_l, sin_l = slice_rope_for_cp(cos, sin, s_local, cfg)
    valid = layer_valid_mask(params["layers"], cfg)

    if valid is None:
        def body(h, lp):
            return decoder_layer(lp, h, cos_l, sin_l, cfg), h
        scan_xs = params["layers"]
    else:
        def body(h, xs):
            lp, v = xs
            return jnp.where(v, decoder_layer(lp, h, cos_l, sin_l, cfg), h), h
        scan_xs = (params["layers"], valid)
    if vma_checking("pp"):
        h = scan_carry_fixpoint(
            body, h, jax.tree.map(lambda a: a[0], scan_xs))
    h_final, layer_inputs = lax.scan(body, h, scan_xs)
    loss = _stage_loss(params, h_final, targets, cfg, is_last)
    # h_final IS buffered (not rederived from layer_inputs[-1] inside the
    # last-stage cond in stage_bwd): with cp>1 the rederiving decoder_layer
    # would put ring-attention ppermutes inside a partially-executed
    # conditional, which the XLA CPU runtime's global collective-permute
    # rendezvous aborts on (utils.collective_scan_unroll). psums inside
    # conds (embed/loss gating) are per-group rendezvous and safe.
    return h_final, loss, {"layer_inputs": layer_inputs, "h_final": h_final}


def stage_bwd(params, saved, tokens, targets, dh_out, dloss, cos, sin,
              cfg: Config, is_first=None, is_last=None):
    """Manual backward for one stage: given the saved layer boundaries, the
    downstream cotangent ``dh_out`` and the loss cotangent ``dloss``, return
    (dparams, dh_prev). Each layer's backward re-derives its VJP from the
    saved layer *input* — one forward recompute + backward per layer, i.e.
    exactly remat="full" cost (3x fwd FLOPs), with no whole-stage forward
    rebuild. Head/loss and embedding backwards are cond-gated to the owning
    stages, mirroring ``stage_apply``."""
    pp = cfg.distributed.pp_size
    stage = lax.axis_index("pp")
    pred_first = (stage == 0) if is_first is None else is_first
    pred_last = (stage == pp - 1) if is_last is None else is_last
    dt = jnp.dtype(cfg.model.dtype)
    s_local = tokens.shape[-1]
    cos_l, sin_l = slice_rope_for_cp(cos, sin, s_local, cfg)

    # ---- head/loss backward (last stage only)
    h_final = saved["h_final"]

    def loss_head(fn_w, lm_w, h):
        return loss_from_hidden({"final_norm": fn_w, "lm_head": lm_w}, h,
                                targets, cfg)

    def loss_vjp():
        out, vjp = jax.vjp(loss_head, params["final_norm"], params["lm_head"],
                           h_final)
        # vma cast: the schedule's dloss mask is built from pp-index
        # predicates only; the cotangent type must match the primal loss
        # (check_vma)
        return vjp(pvary_like(dloss, out))

    if _stage_gating(cfg):
        # cond gating + check_vma is rejected at validation; no casts here
        d_fnorm, d_lmhead, dh_loss = lax.cond(
            pred_last,
            loss_vjp,
            lambda: (jnp.zeros_like(params["final_norm"]),
                     jnp.zeros_like(params["lm_head"]),
                     jnp.zeros_like(h_final)),
        )
    else:
        # dloss is already masked to the last stage, and the vjp outputs are
        # linear in dloss, so no further masking is needed
        d_fnorm, d_lmhead, dh_loss = loss_vjp()
    dh = dh_out + dh_loss

    # ---- layers backward: reverse scan re-deriving each layer's VJP from its
    # saved input (ys keep xs order under reverse=True). Pad rows of an
    # uneven split mirror the forward's where-skip: cotangent passes through,
    # the pad layer's grads are zeroed.
    valid = layer_valid_mask(params["layers"], cfg)

    def layer_bwd(dh, xs):
        lp, x, v = xs
        _, vjp = jax.vjp(lambda lp, h: decoder_layer(lp, h, cos_l, sin_l, cfg),
                         lp, x)
        dlp, dx = vjp(dh)
        if valid is not None:
            dlp = jax.tree.map(lambda g: jnp.where(v, g, 0), dlp)
            dx = jnp.where(v, dx, dh)
        return dx, dlp

    n_rows = jax.tree.leaves(params["layers"])[0].shape[0]
    vmask = (jnp.ones(n_rows, bool) if valid is None else valid)
    dh, d_layers = lax.scan(layer_bwd, dh,
                            (params["layers"], saved["layer_inputs"], vmask),
                            reverse=True)

    # ---- embedding backward (first stage only)
    def embed_vjp():
        # vma cast on w: dh carries the schedule's pp-varying type while
        # the embed output would not, and a vjp cotangent must match its
        # primal exactly (check_vma); numerically the identity
        out, vjp = jax.vjp(
            lambda w: embed_lookup(w, tokens, use_sp(cfg)).astype(dt),
            pvary_like(params["embed"], dh))
        return vjp(pvary_like(dh, out))[0]

    if _stage_gating(cfg):
        # cond gating + check_vma is rejected at validation; no casts here
        d_embed = lax.cond(pred_first, embed_vjp,
                           lambda: jnp.zeros_like(params["embed"]))
    else:
        d_embed = jnp.where(pred_first, embed_vjp(), 0)
    dh_prev = jnp.where(pred_first, jnp.zeros_like(dh), dh)
    dparams = {"embed": d_embed, "layers": d_layers,
               "final_norm": d_fnorm, "lm_head": d_lmhead}
    return dparams, dh_prev


def forward_logits(params, tokens, cfg: Config, gather: bool = True,
                   seq_layout: str | None = None):
    """Whole-model forward to logits (no pipeline), for eval/tests. Runs inside
    shard_map; with a 1-device mesh this is the plain single-chip model.

    Zigzag layout contract: when ``cfg.distributed.cp_zigzag`` is set, the
    RoPE tables and causal masks follow the zigzag *data* layout, so
    ``tokens`` must already be permuted the way the training loader permutes
    them (``parallel.cp.zigzag_perm`` applied to the GLOBAL sequence axis,
    before any cp sharding), and the returned logits are in that same
    permuted order — apply ``parallel.cp.zigzag_inverse_perm`` to get
    original-order logits. The caller acknowledges this by passing
    ``seq_layout="zigzag"``; a zigzag config without it raises rather than
    silently computing with wrong positions/masks. (The permutation cannot
    be applied here: under cp>1 this function sees only a local sequence
    shard, while the permutation is global.)

    Interleaved layer layouts (pp_interleave > 1) are remapped to the
    contiguous global order on the fly (``remap_layout`` — a pure row
    permutation, since interleave requires L % (pp*v) == 0), so
    interleaved-trained params eval directly."""
    d = cfg.distributed
    zig = d.cp_zigzag and d.cp_size > 1
    if zig and seq_layout != "zigzag":
        raise ValueError(
            "this config trains with the zigzag sequence layout "
            "(cp_zigzag): pass seq_layout='zigzag' after permuting the "
            "global sequence axis with parallel.cp.zigzag_perm (invert "
            "logits with zigzag_inverse_perm) — original-order tokens "
            "would silently get wrong positions/masks")
    if not zig and seq_layout == "zigzag":
        raise ValueError(
            "seq_layout='zigzag' passed but the config does not use the "
            "zigzag layout (cp_zigzag with cp_size > 1)")
    if d.pp_interleave > 1 and d.pp_size > 1:
        params = remap_layout(params, cfg.model.num_hidden_layers,
                              (d.pp_size, d.pp_interleave))
    cos, sin = rope_tables(cfg)
    dt = jnp.dtype(cfg.model.dtype)
    h = embed_lookup(params["embed"], tokens, use_sp(cfg)).astype(dt)
    s_local = tokens.shape[-1]
    cos_l, sin_l = slice_rope_for_cp(cos, sin, s_local, cfg)
    h = layers_forward(params["layers"], h, cos_l, sin_l, cfg)
    logits = head_logits(params, h, cfg)
    return tp_gather(logits) if gather else logits


def num_params(m: ModelConfig) -> int:
    """Global parameter count (the reference reconstructs this across shards,
    utils.py:52-79; here it's arithmetic)."""
    H, I, V, L, D = (m.hidden_size, m.intermediate_size, m.vocab_size,
                     m.num_hidden_layers, m.head_dim)
    per_layer = (H * m.num_attention_heads * D + 2 * H * m.num_key_value_heads * D
                 + m.num_attention_heads * D * H + 3 * H * I + 2 * H)
    return V * H + L * per_layer + H + H * V
