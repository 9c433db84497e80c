"""The batched generation engine: jitted prefill / decode programs on the mesh.

Serving counterpart of ``train_step.py``. Three compiled program families
cover a request's whole life:

- ``prefill(params, prompt)``: the full-sequence model (the SAME
  ``decoder_layer`` path training runs, flash-capable on TPU) over a
  right-padded prompt bucket, returning the per-layer compact K/V blocks
  (quantized for int8 caches) plus the last real token's full-vocab logits.
  Prompts are padded to power-of-two buckets so arbitrary lengths reuse a
  handful of compiled shapes; pad rows are inert (causal mask ahead, length
  mask behind). Prompts longer than ``prefill_chunk`` instead run
  ``prefill_chunked``: fixed-width chunk dispatches that attend causally
  over the already-written cache prefix plus the current chunk and write
  K/V straight into the target slot — O(1) compiled shapes in prompt
  length, flat peak activation memory.
- ``decode_block(params, cache, tokens, keys, eos_id, budget, ...)``:
  ``decode_block_len`` autoregressive steps for EVERY slot inside ONE
  jitted program (``lax.scan`` over steps). Per-slot stop state lives on
  device — ``eos_id`` [B] (−1 = none), remaining-token ``budget`` [B], and
  the active mask derived from ``cache['lengths']`` — so a slot that hits
  EOS or exhausts its budget mid-block goes inactive, emits pad tokens,
  and stops advancing its cache length: the block result is exactly what
  that many single steps would have produced. One host sync per block
  instead of per token. ``decode_block_len == 1`` is the classic per-token
  loop.
- ``decode_step(...)``: the single-token program (kept for callers that
  want per-token logits; the batcher drives ``decode_block``).
- ``verify(params, cache, tokens, key, ...)`` (``spec_len > 0``): the
  speculative-decoding verify pass — ONE dispatch scores ``spec_len + 1``
  positions per slot (each slot's last token plus ``spec_len`` drafted
  continuation tokens), writing the drafted K/V into the slot
  OPTIMISTICALLY (int8 caches quantize on write as always), then applies
  the distribution-preserving acceptance rule on device
  (sampling.speculative_accept) and rewinds each slot's length pointer to
  its accepted prefix — the rejected rows become stale K/V beyond the
  length mask, exactly like a freed slot's. Each dispatch emits 1 to
  ``spec_len + 1`` tokens per slot.

Sharding: the engine builds (or is handed) a ``('dp','pp','cp','tp')`` mesh
with dp=pp=cp=1 and runs the programs under shard_map with the model's
training PartitionSpecs — a TP-sharded checkpoint loads and decodes without
resharding; the cache's head axis (and the int8 scale tensors' head axis)
shards over 'tp' alongside the wk/wv columns that fill it. Pipeline- or
interleave-trained checkpoints are handled at LOAD time
(checkpoint.CheckpointManager.load / load_params remap stacked layer rows
to the contiguous pp=1 layout), so the engine always sees a plain [L] stack.

The cache is donated through every decode/insert/chunk program, so
steady-state generation updates the K/V buffers in place — no per-token
reallocation.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from picotron_tpu import comm_trace, models
from picotron_tpu.config import ATTEND_IMPLS, Config
from picotron_tpu.inference import kv_cache, paged_kv, sampling
from picotron_tpu.obs import Obs
from picotron_tpu.models import llama
from picotron_tpu.ops.rope import rope_at_positions
from picotron_tpu.parallel.tp import tp_gather
from picotron_tpu.topology import Topology, build_topology, named_shardings
from picotron_tpu.utils import log0, on_tpu, shard_map

# Process-wide graceful-degradation latch (inference.attend_fallback): once
# a flash dispatch has failed, every engine in this process — current and
# future — serves on "dense". A kernel that broke once is not re-trusted
# mid-serve; restarting the process is the way to re-arm flash.
_FLASH_BROKEN = False


def _runs_flash(impl: str) -> bool:
    """Whether ``attend_impl`` puts a flash-decode kernel on some path of
    this process: "flash" everywhere, "auto" on a TPU (off it "auto" is
    "dense": nothing to degrade from)."""
    return impl == "flash" or (impl == "auto" and on_tpu())


# a round's [slots] host rows in the order they close its packed operand
# (``_round_operands``), after the tokens and a verify's ``valid``; the two
# float32 rows ride as their bits
_PACK_ROWS = ("eos_id", "budget", "top_k", "temperature", "top_p")

# Rows of the chunk program a short remainder runs, behind the prefix store
# (``InferenceEngine.chunk_width``): the largest multiple of the MXU's 128-row
# tile under the v5e's ridge (197 TFLOP/s over 819 GB/s: ~240 rows of bf16),
# so such a chunk costs the read of the weights and no more, where a
# ``prefill_chunk`` of 512 rows costs four times that for the same few tokens.
NARROW_CHUNK = 128


def _key_chain(key, block: int):
    """``block`` links of the batcher's key chain in one trace: each link
    is the eager ``key, sub = jax.random.split(key)``, so the carried key
    and the ``[block, 2]`` subkeys equal the eager loop's bit for bit."""
    def link(k, _):
        k, sub = jax.random.split(k)
        return k, sub
    return lax.scan(link, key, None, length=block)


class RoundResult(NamedTuple):
    """What one round dispatch (``decode_block`` | ``verify``) returns.
    ``cache``, ``next_tok``, ``hidden`` and ``lane`` are device arrays (or
    pytrees) still in flight, None where the engine's options produce
    nothing. What the host reads every round comes down as ONE int32 array,
    ``packed`` [slots, n + 1 (+ 1)], whose copy to the host was started at
    issue: ``tokens``, ``counts`` and ``accepted`` are host views of that
    copy, and reading one waits for it."""
    cache: dict
    # [slots, n tokens | counts | accepted (a verify's)], int32
    packed: jax.Array
    verify: bool = False
    # key_schedule "slot": each slot's post-round last token [slots]
    next_tok: Optional[jax.Array] = None
    # return_hidden: [slots, H] at each slot's last emitted position
    hidden: Optional[jax.Array] = None
    # mixed_dispatch: (the lane's sampled token [dp] or logits [dp, V],
    # the lane's hidden [dp, H] or None)
    lane: Optional[tuple] = None

    def host(self) -> tuple:
        """(tokens [slots, n], counts [slots], accepted [slots] or None)
        on the host: views of the one packed copy (waited for here)."""
        out = np.asarray(self.packed)
        if self.verify:
            return out[:, :-2], out[:, -2], out[:, -1]
        return out[:, :-1], out[:, -1], None

    @property
    def tokens(self) -> np.ndarray:
        """[slots, decode_block_len | spec_len + 1]"""
        return self.host()[0]

    @property
    def counts(self) -> np.ndarray:
        """[slots]: leading entries of each tokens row"""
        return self.host()[1]

    @property
    def accepted(self) -> Optional[np.ndarray]:
        """verify: drafts accepted [slots]"""
        return self.host()[2]


def inference_config(cfg: Config) -> Config:
    """Derive the serving config from a training config: same model, but a
    ('dp','tp') topology (pp=cp=1) with the training-only rewrites (sequence
    parallelism, fsdp/zero1, vma checking) off — none of them make sense at
    query length 1, and sequence parallelism cannot even shard it. The
    serving dp width comes from ``inference.dp_size`` (NOT the training
    ``distributed.dp_size``, which shards gradients, not slots); 1 — the
    default — is the historical tp-only mesh."""
    raw = cfg.to_dict()
    dp = int((raw.get("inference") or {}).get("dp_size", 1) or 1)
    raw["distributed"].update(dict(
        dp_size=dp, pp_size=1, cp_size=1, pp_interleave=1,
        tp_sequence_parallel=False, fsdp=False, zero1=False,
        check_vma=False, cp_zigzag=False))
    return Config.from_dict(raw)


class InferenceEngine:
    """Fixed-slot generation engine over a tp mesh.

    ``slots`` is the decode batch width: the continuous batcher admits and
    retires requests into these fixed positions so the compiled decode
    program never changes shape. ``max_seq_len`` bounds prompt + generated
    tokens per slot (default: the model's max_position_embeddings).
    ``decode_block_len`` / ``kv_cache_dtype`` / ``prefill_chunk`` /
    ``attend_impl`` default from ``cfg.inference`` (config.InferenceConfig);
    keyword overrides win. ``attend_impl="flash"`` routes every cache
    attend (decode, verify, chunked prefill) through the length-aware
    Pallas flash-decode kernels instead of the dense whole-window einsum;
    the default ``"auto"`` does so for the plain decode step on a TPU
    (``kv_cache.attend``).
    """

    def __init__(self, cfg: Config, topo: Optional[Topology] = None, *,
                 slots: int = 8, max_seq_len: Optional[int] = None,
                 cache_dtype=None, min_prefill_bucket: int = 16,
                 decode_block_len: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 spec_len: Optional[int] = None,
                 spec_ngram: Optional[int] = None,
                 attend_impl: Optional[str] = None,
                 kv_layout: Optional[str] = None,
                 kv_page_len: Optional[int] = None,
                 kv_num_pages: Optional[int] = None,
                 kv_page_policy: Optional[str] = None,
                 kv_store_pages: Optional[int] = None,
                 sample_on_device: Optional[bool] = None,
                 weight_dtype: Optional[str] = None,
                 drafter: Optional[str] = None,
                 return_hidden: Optional[bool] = None,
                 overlap: Optional[bool] = None,
                 mixed_dispatch: Optional[bool] = None,
                 key_schedule: Optional[str] = None,
                 hooks=None, adapters=None):
        self.cfg = inference_config(cfg)
        m, d = self.cfg.model, self.cfg.distributed
        inf = self.cfg.inference
        # the block this engine serves (models/__init__.py: the seam).
        # ``_n_stats`` > 0: its layers count, and every program of the
        # prefill and decode families returns one more small array, a row
        # of counters a layer (``take_stats``)
        self.model = models.model_module(m)
        self._n_stats = len(self.model.STAT_NAMES)
        # how the block generates (its ``GENERATES``): a token a step, or
        # whole blocks of ``model.block_length`` positions a round
        # (``_blocks_impl``; docs/INFERENCE.md "Rounds of blocks")
        self.blocks = getattr(self.model, "GENERATES", "tokens") == "blocks"
        # what ``take_stats`` hands over, (name, labels) each: the layers'
        # counters and, behind them, what a round of blocks counts itself
        self.stat_names = tuple((n, {}) for n in self.model.STAT_NAMES) \
            + (sampling.DIFFUSION_STATS if self.blocks else ())
        self._stats_pending: list = []
        self.dp_size = int(inf.dp_size or 1)
        if self.dp_size < 1:
            raise ValueError("inference.dp_size must be >= 1")
        if topo is None:
            topo = build_topology(self.dp_size, 1, 1, d.tp_size)
        if (topo.pp_size, topo.cp_size) != (1, 1) \
                or topo.dp_size != self.dp_size:
            raise ValueError(
                "InferenceEngine serves a ('dp','tp') mesh (pp=cp=1) whose "
                f"dp width matches inference.dp_size={self.dp_size}; got "
                f"dp={topo.dp_size} pp={topo.pp_size} cp={topo.cp_size}. "
                "Set inference.dp_size to shard ONE logical engine's slot "
                "axis over dp shards (1 = the tp-only default; scale-out "
                "beyond that is still one engine per replica behind the "
                "router).")
        if topo.tp_size != d.tp_size:
            raise ValueError(
                f"mesh tp={topo.tp_size} != config tp_size={d.tp_size}")
        self.topo = topo
        self.slots = int(slots)
        if self.slots % self.dp_size:
            raise ValueError(
                f"slots ({self.slots}) must divide evenly over "
                f"inference.dp_size ({self.dp_size}) — each dp shard "
                "serves slots/dp of the batch")
        self.slots_per_shard = self.slots // self.dp_size
        # optional ClusterMonitor lease guard (attach_monitor): multi-host
        # dp serving checks peer liveness before every dispatch collective
        self.monitor = None
        self.max_seq_len = int(max_seq_len or m.max_position_embeddings)
        self.min_prefill_bucket = int(min_prefill_bucket)
        self.decode_block_len = int(decode_block_len
                                    if decode_block_len is not None
                                    else inf.decode_block_len)
        if self.decode_block_len < 1:
            raise ValueError("decode_block_len must be >= 1")
        self.prefill_chunk = int(prefill_chunk if prefill_chunk is not None
                                 else inf.prefill_chunk)
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.spec_len = int(spec_len if spec_len is not None
                            else inf.spec_len)
        if self.spec_len < 0:
            raise ValueError("spec_len must be >= 0 (0 = off)")
        self.spec_ngram = int(spec_ngram if spec_ngram is not None
                              else inf.spec_ngram)
        if self.spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1")
        # Drafter selection (inference.drafter): "ngram" keeps drafting
        # host-side; "learned" is the EAGLE-style head over the target's
        # last hidden state, which needs that state plumbed out of every
        # dispatch — the return_hidden hook below (PR 1's return_kv
        # pattern: a trace-time output the programs grow only when asked).
        if drafter is not None:
            if drafter not in ("ngram", "learned"):
                raise ValueError(
                    f"unknown drafter {drafter!r} (ngram|learned)")
            inf.drafter = drafter
        self.drafter_kind = inf.drafter
        if return_hidden is None:
            return_hidden = (self.spec_len > 0
                             and self.drafter_kind == "learned")
        self.return_hidden = bool(return_hidden)
        # KV-cache attention kernel for decode/verify/chunked prefill:
        # "auto" (the flash-decode kernel for the plain decode step on a
        # TPU, dense elsewhere: kv_cache.attend), "dense" (whole-window
        # reference) or "flash" (the Pallas kernels everywhere). A
        # Python-level choice, so every jitted program below traces the
        # selected kernel statically — no runtime branch, one executable
        # per impl. The override lands in self.cfg BEFORE the jit wrappers
        # close over it.
        if attend_impl is not None:
            if attend_impl not in ATTEND_IMPLS:
                raise ValueError(
                    f"unknown attend_impl {attend_impl!r} "
                    f"({'|'.join(ATTEND_IMPLS)})")
            inf.attend_impl = attend_impl
        if (_runs_flash(inf.attend_impl) and inf.attend_fallback
                and _FLASH_BROKEN):
            # the process-wide degradation latch: flash already failed here
            log0(f"attend_impl {inf.attend_impl!r}: the flash kernel "
                 "already failed in this process; this engine starts on "
                 "'dense' (inference.attend_fallback)")
            inf.attend_impl = "dense"
        self.attend_impl = inf.attend_impl
        # Fused on-device sampling epilogue: prefill/chunked-prefill/
        # decode_step dispatches sample INSIDE the jitted program and
        # return token ids instead of [*, vocab] logits (decode_block and
        # verify always did). A trace-time choice like attend_impl: the
        # programs below are built with or without the epilogue.
        if sample_on_device is not None:
            inf.sample_on_device = bool(sample_on_device)
        self.sample_on_device = inf.sample_on_device
        # Zero-bubble overlapped scheduling + PRNG key schedule
        # (docs/INFERENCE.md "Overlapped scheduling"). overlap is the
        # BATCHER's pipeline switch; the engine carries it so the batcher,
        # serve front end, and bench all read one resolved source of
        # truth. key_schedule decides how sampled tokens are keyed:
        # "round" (one fresh key per dispatch — the historical schedule)
        # or "slot" (token at position p keyed fold_in(base_slot, p-1) —
        # round-structure-independent, which is what lets the pipeline
        # reorder rounds without moving a single sampled token). "auto"
        # resolves to "slot" iff overlap is on, so the default-off path
        # keeps today's programs byte-identical.
        if overlap is not None:
            inf.overlap = bool(overlap)
        if mixed_dispatch is not None:
            inf.mixed_dispatch = bool(mixed_dispatch)
        if key_schedule is not None:
            inf.key_schedule = key_schedule
        self.overlap = bool(inf.overlap)
        # Mixed prefill–decode dispatch (docs/INFERENCE.md "Mixed
        # prefill–decode dispatch"): every decode/verify dispatch also
        # advances one fixed-width prefill LANE (prefill_chunk tokens,
        # padded/masked when idle so the compiled shape never changes).
        # Like overlap, mixed streams must be keyed per slot so the lane's
        # round placement cannot move a sampled token.
        self.mixed = bool(inf.mixed_dispatch)
        ks = inf.key_schedule
        if ks not in ("auto", "round", "slot"):
            raise ValueError(
                f"unknown key_schedule {ks!r} (auto|round|slot)")
        if ks == "auto":
            ks = "slot" if (self.overlap or self.mixed) else "round"
        elif ks == "round" and self.overlap:
            raise ValueError(
                "overlap requires the per-slot key schedule — round-keyed "
                "sampling ties streams to round boundaries; use "
                "key_schedule='slot' (or 'auto')")
        elif ks == "round" and self.mixed:
            raise ValueError(
                "mixed_dispatch requires the per-slot key schedule — "
                "round-keyed sampling ties streams to round boundaries, "
                "which fusing the prefill lane changes; use "
                "key_schedule='slot' (or 'auto')")
        self.key_schedule = ks
        # Deferred paged length advance: the overlapped batcher's sync
        # stage owns host_len bookkeeping (apply_advance) because at issue
        # time the previous round's counts are still on device. Off by
        # default; ContinuousBatcher flips it when it runs the pipeline.
        self.defer_advance = False
        # Weight storage format (inference.weight_dtype): "bf16" keeps the
        # dense params tree; "int8" expects the per-channel quantized tree
        # (checkpoint.load_* with weight_dtype="int8", or
        # llama.quantize_params) — every matmul site dispatches on the
        # LEAF form at trace time (models/llama.py::matmul), so the only
        # engine-side difference is the pspec tree shard_params places
        # against (scales shard over 'tp' with their channels).
        if weight_dtype is not None:
            if weight_dtype not in ("bf16", "int8"):
                raise ValueError(
                    f"unknown weight_dtype {weight_dtype!r} (bf16|int8)")
            inf.weight_dtype = weight_dtype
        self.weight_dtype = inf.weight_dtype
        self.quant_weights = self.weight_dtype == "int8"
        # Telemetry (picotron_tpu/obs, docs/OBSERVABILITY.md): every
        # engine owns a fresh metrics registry (counters start at zero
        # per server) and shares the process span ring. The batcher and
        # serve front end reuse this bundle, so one /metrics page covers
        # the whole serving stack. obs.enabled: false swaps in no-ops.
        self.obs = Obs.from_config(self.cfg.obs)
        # dispatch hooks (fault injection / observation): an object with
        # before_dispatch(kind, active_slots) — may raise or sleep — and
        # poison_logits(kind) -> bool (route this dispatch through the
        # NaN-poisoned decode program). resilience.chaos.ServingChaos is
        # the shipped implementation; None = no hooks.
        self.hooks = hooks
        # a chunk wider than the cache window could never be written
        # (mirrors prefill_bucket's min(bucket, max_seq_len) cap)
        self.prefill_chunk = min(self.prefill_chunk, self.max_seq_len)
        # int8 is accepted through either the config knob or cache_dtype
        # (string "int8", jnp.int8, or np.dtype — normalized, so the dtype
        # spelling can't silently build an unquantized int8 cache); an
        # EXPLICIT cache_dtype wins over the config, so a caller can turn
        # quantization off as well as on
        if cache_dtype is not None:
            self.quantized = jnp.dtype(cache_dtype) == jnp.dtype(jnp.int8)
        else:
            self.quantized = inf.kv_cache_dtype == "int8"
        self.cache_dtype = (jnp.dtype(jnp.int8) if self.quantized
                            else jnp.dtype(cache_dtype or m.dtype))
        self._dt = jnp.dtype(m.dtype)

        # KV memory layout: "contiguous" (per-slot strips — the pinned
        # default) or "paged" (block-table indirection over a global page
        # pool with refcounted prefix sharing + copy-on-write —
        # inference/paged_kv.py). A Python-level choice like attend_impl:
        # every jitted program traces the selected layout statically.
        if kv_layout is not None:
            if kv_layout not in ("contiguous", "paged"):
                raise ValueError(
                    f"unknown kv_layout {kv_layout!r} (contiguous|paged)")
            inf.kv_layout = kv_layout
        self.kv_layout = inf.kv_layout
        # Per-page storage policy (hot_bf16: shared pages read full
        # precision, exclusive tails read int8) — paged-only, mutually
        # exclusive with a uniformly int8 cache (config.validate mirrors
        # both checks for the JSON path; the kwargs path lands here).
        if kv_page_policy is not None:
            if kv_page_policy not in ("uniform", "hot_bf16"):
                raise ValueError(
                    f"unknown kv_page_policy {kv_page_policy!r} "
                    "(uniform|hot_bf16)")
            inf.kv_page_policy = kv_page_policy
        self.kv_page_policy = inf.kv_page_policy
        if self.kv_page_policy == "hot_bf16":
            if self.kv_layout != "paged":
                raise ValueError(
                    "kv_page_policy 'hot_bf16' requires kv_layout='paged' "
                    "(per-page refcounts decide which pages read as int8); "
                    "set kv_layout='paged' or keep kv_page_policy="
                    "'uniform'")
            if self.quantized:
                raise ValueError(
                    "kv_page_policy 'hot_bf16' is mutually exclusive with "
                    "an int8 cache (it manages its own quantized "
                    "representation); drop cache_dtype/kv_cache_dtype "
                    "'int8' or keep kv_page_policy='uniform'")
        self.page_policy = self.kv_page_policy == "hot_bf16"
        self.paged: Optional[paged_kv.PagedKV] = None
        if self.kv_layout == "paged":
            self.page_len = int(kv_page_len or inf.kv_page_len)
            if self.page_len < 8 or self.page_len & (self.page_len - 1):
                raise ValueError(
                    f"kv_page_len must be a power of two >= 8, got "
                    f"{self.page_len}")
            # logical window per slot, in pages (>= max_seq_len rows)
            self.max_pages = -(-self.max_seq_len // self.page_len)
            self.num_pages = int(
                kv_num_pages or inf.kv_num_pages
                or self.dp_size * (1 + self.slots_per_shard
                                   * self.max_pages))
            if self.num_pages < 2:
                raise ValueError("kv_num_pages must be >= 2 "
                                 "(page 0 is the reserved NULL page)")
            if self.dp_size > 1:
                # dp-sharded pool: each shard runs its own PagedKV over a
                # pages_per_shard strip (local page 0 = that shard's NULL
                # page); the engine sees global slot/page ids through the
                # ShardedPagedKV facade.
                self.paged = paged_kv.ShardedPagedKV(
                    self.dp_size, self.slots, self.page_len, self.max_pages,
                    self.num_pages, prefix_cache=inf.prefix_cache)
                self.pages_per_shard = self.paged.pages_per_shard
            else:
                self.paged = paged_kv.PagedKV(
                    self.slots, self.page_len, self.max_pages,
                    self.num_pages, prefix_cache=inf.prefix_cache)
                self.pages_per_shard = self.num_pages

        if m.model_type != "llama":
            # what another block cannot do yet is refused by name
            # (``Config.validate``), whether it was asked for in the config
            # or by a keyword here
            inf.spec_len = self.spec_len
            inf.prefill_chunk = self.prefill_chunk
            inf.decode_block_len = self.decode_block_len
            if self.blocks and self.max_seq_len % max(m.block_length, 1):
                raise ValueError(
                    f"model_type {m.model_type!r} generates by blocks: "
                    f"max_seq_len ({self.max_seq_len}) must be a multiple "
                    f"of block_length ({m.block_length}), or a prompt's "
                    "last chunk slides back off a block boundary")
            self.cfg.validate()
            if adapters is not None or self.quantized:
                raise ValueError(
                    f"model_type {m.model_type!r} serves without adapters "
                    "and with its cache in the model's dtype")
            if getattr(self.model, "CARRIES_STATE", False) \
                    and self.max_seq_len % self.prefill_chunk:
                raise ValueError(
                    f"model_type {m.model_type!r} carries a recurrent state "
                    f"from chunk to chunk: max_seq_len ({self.max_seq_len}) "
                    f"must be a multiple of prefill_chunk "
                    f"({self.prefill_chunk}), or a prompt's last chunk "
                    "slides back inside the window and feeds the state its "
                    "overlap twice (prefill_chunked)")
        # angle tables cover the whole cache window; decode gathers rows at
        # each slot's own offset
        self._cos, self._sin = self.model.serving_rope_tables(
            m, self.max_seq_len, self._dt)

        self._pspecs = self.model.param_pspecs(
            m, weight_dtype=self.weight_dtype)
        # Multi-tenant adapter pack (inference/tenancy.py): when present,
        # every dispatch binds per-row adapter ids into the params tree
        # (llama.bind_adapters) and the compiled programs grow the
        # adapter operands — a trace-time leaf-form change on the same
        # seam weight quantization rides, so adapter-less engines build
        # byte-identical programs to the pre-tenancy engine.
        # ``shard_params`` keeps placing the BASE tree (self._pspecs);
        # only the dispatch in_specs see the wrapped form.
        self.adapters = adapters
        self._dispatch_pspecs = self._pspecs
        if adapters is not None:
            from picotron_tpu.inference import tenancy
            if adapters.dims != tenancy.adapter_dims(m):
                raise ValueError(
                    f"adapter pack built for dims {adapters.dims} but this "
                    f"model has {tenancy.adapter_dims(m)} — packs are "
                    f"model-shape specific")
            if adapters.rows != m.num_hidden_layers:
                raise ValueError(
                    f"adapter pack has {adapters.rows} layer rows; the "
                    f"serving stack holds {m.num_hidden_layers}")
            self._dispatch_pspecs = llama.adapter_pspecs(self._pspecs)
            self._adapter_sh = named_shardings(topo, {
                name: {"a": self._dispatch_pspecs["layers"][name]["a"],
                       "b": self._dispatch_pspecs["layers"][name]["b"]}
                for name in llama.QUANT_WEIGHT_LEAVES})
        # the decode-family dispatches shard their per-slot [B] operands
        # over dp — the adapter ids bound into the params tree ([L, B],
        # one row per GLOBAL slot) must shard with them, while one-shot
        # prefill (B=1, fully replicated) keeps the plain form
        self._decode_dispatch_pspecs = self._dispatch_pspecs
        if adapters is not None and self.dp_size > 1:
            layers = dict(self._dispatch_pspecs["layers"])
            for name in llama.QUANT_WEIGHT_LEAVES:
                layers[name] = {**layers[name], "ids": P("pp", "dp")}
            self._decode_dispatch_pspecs = {**self._dispatch_pspecs,
                                            "layers": layers}
        if self.paged is not None:
            self._cspecs = paged_kv.cache_pspecs(self.quantized,
                                                 policy=self.page_policy,
                                                 dp=self.dp_size)
        else:
            self._cspecs = self.model.cache_pspecs(m, self.quantized,
                                                   dp=self.dp_size)
        self._build_programs()
        # the round schedule's keys (``round_keys``): made and kept on the
        # device, replicated over the mesh as the round programs take them
        self.key_sharding = named_shardings(topo, P())
        self._round_keys_jit = jax.jit(
            _key_chain, static_argnums=1,
            out_shardings=(self.key_sharding, self.key_sharding))
        # kv_cache.release works on both layouts (a paged release is the
        # same 1-element length write; the host manager frees the pages)
        # dp>1: pin cache-shaped outputs of the host-side helper jits to
        # the dp-sharded cache layout so donation round-trips never leave
        # a leaf gathered; dp=1 keeps them unconstrained (byte-identical
        # programs to the tp-only engine).
        cache_sh = (named_shardings(topo, self._cspecs)
                    if self.dp_size > 1 else None)
        self._release_jit = jax.jit(kv_cache.release, donate_argnums=(0,),
                                    out_shardings=cache_sh)
        if self.paged is not None:
            self._insert_jit = jax.jit(paged_kv.insert_prefill,
                                       donate_argnums=(0,),
                                       out_shardings=cache_sh)
            self._copy_page_jit = jax.jit(paged_kv.copy_page,
                                          donate_argnums=(0,),
                                          out_shardings=cache_sh)
            # page-transport device ops (inference/page_transport.py):
            # built ONCE here — a per-page jit build would recompile every
            # import (picolint PICO-J004's exact hazard). Export reads and
            # import writes are each ONE batched (pow-2-bucketed)
            # dispatch: an export pays one host sync however long the
            # prefix, and an import fault can only land BEFORE the
            # cache-donating dispatch, never mid-batch.
            self._slice_page_jit = jax.jit(paged_kv.slice_page)
            self._gather_pages_jit = jax.jit(paged_kv.gather_pages)
            self._write_pages_jit = jax.jit(paged_kv.write_pages,
                                            donate_argnums=(0,),
                                            out_shardings=cache_sh)
            self._set_length_jit = jax.jit(paged_kv.set_length,
                                           donate_argnums=(0,),
                                           out_shardings=cache_sh)
            self._init_cache_jit = jax.jit(
                partial(paged_kv.init_cache, m, self.slots, self.num_pages,
                        self.page_len, self.max_pages,
                        dtype=self.cache_dtype, quantized=self.quantized,
                        policy=self.page_policy),
                out_shardings=named_shardings(topo, self._cspecs))
        else:
            self._insert_jit = jax.jit(kv_cache.insert_prefill,
                                       donate_argnums=(0,),
                                       out_shardings=cache_sh)
            # a block that keeps rings sizes them by the chunk they must fit
            ring = ({"prefill_chunk": self.prefill_chunk}
                    if getattr(self.model, "RING_CACHE", False) else {})
            self._init_cache_jit = jax.jit(
                partial(self.model.init_cache, m, self.slots,
                        self.max_seq_len, dtype=self.cache_dtype,
                        quantized=self.quantized, tp=topo.tp_size, **ring),
                out_shardings=named_shardings(topo, self._cspecs))
        # what the cache is, read off its own shapes: the heads a row of
        # K and V holds (kv_cache.pack_factor; None without such leaves)
        # and the bytes resident for the life of the server
        shapes = jax.eval_shape(self._init_cache_jit)
        self._leaf_dtypes = {n: a.dtype for n, a in shapes.items()
                             if n not in paged_kv.META_LEAVES}
        self.kv_pack = kv_cache.kv_pack(shapes, m.head_dim)
        self.kv_cache_bytes = kv_cache.cache_bytes(shapes)
        if self.kv_pack is not None:
            self.obs.registry.gauge(
                "picotron_kv_pack_factor",
                "kv heads stored side by side in one cache row").set(
                    self.kv_pack)
        self.obs.registry.gauge(
            "picotron_kv_cache_bytes",
            "bytes of the resident KV cache leaves").set(
                self.kv_cache_bytes)
        # The prefix store beside the strips (docs/SERVING.md "Prompt
        # reuse on the contiguous layout"): finished prompts' whole pages
        # kept in a side pool under a radix trie, copied back into the slot
        # of a later prompt that begins with them (``prefill_stored``).
        # Host half ``self.store``; device half ``self._store_pool``,
        # allocated with the first cache (``init_cache``) and kept across
        # caches; None where ``_store_pages`` finds no place for it.
        if kv_store_pages is not None:
            inf.kv_store_pages = int(kv_store_pages)
        self.store: Optional[paged_kv.PrefixStore] = None
        self._store_pool = None
        # retentions owed, (prompt ids, slot, salt): copied in the shadow
        # of the next round (``_store_flush``), not inside admission
        self._store_pending: list = []
        page_len = int(kv_page_len or inf.kv_page_len)
        pages = self._store_pages(shapes, page_len)
        if pages:
            self.store = paged_kv.PrefixStore(
                page_len, -(-self.max_seq_len // page_len), pages)
            full = named_shardings(topo, self._cspecs)
            pool_sh = {n: full[n] for n in kv_cache.store_leaves(shapes)}
            self._init_store_jit = jax.jit(
                partial(kv_cache.init_store, shapes, pages, page_len),
                out_shardings=pool_sh)
            # both take their arrays under the cache's own shardings
            # whoever made them (a fresh cache, a round, a release), so
            # the one compile of ``init_cache`` serves every admission
            self._retain_jit = jax.jit(
                kv_cache.retain_rows, donate_argnums=(0,),
                in_shardings=(pool_sh, full, None, None),
                out_shardings=pool_sh)
            self._seat_jit = jax.jit(
                kv_cache.seat_rows, donate_argnums=(0,),
                in_shardings=(full, pool_sh, None, None, None),
                out_shardings=full)
            self.store_bytes = kv_cache.cache_bytes(
                jax.eval_shape(self._init_store_jit))
            reg = self.obs.registry
            reg.gauge("picotron_prefix_store_bytes",
                      "bytes of the prefix store's page pool").set(
                          self.store_bytes)
            self._store_counters = {
                what: reg.counter(f"picotron_prefix_store_{what}_total", doc)
                for what, doc in (
                    ("hits", "admissions that found a retained prefix"),
                    ("tokens_copied", "prompt tokens whose K/V rows were "
                     "copied into the slot and not prefilled"),
                    ("pages_retained", "pages of finished prompts the "
                     "store took"),
                    ("pages_evicted", "retained pages freed for newer "
                     "ones, least recently used first"))}
        # the narrow chunk (``chunk_width``): with the store alone, where a
        # resumed suffix is what an admission prefills; 0: every chunk wide
        self.narrow_chunk = (NARROW_CHUNK if self.store is not None
                             and NARROW_CHUNK < self.prefill_chunk else 0)
        self._narrow_built = False  # ``build_narrow`` ran (once a process)
        reg = self.obs.registry
        self.prefill_rows_total = reg.counter(
            "picotron_prefill_rows_total",
            "rows the prefill programs ran (one-shot buckets, chunks, lane "
            "chunks), padding included")
        self.prefill_chunks_total = {
            w: reg.counter("picotron_prefill_chunks_total",
                           "chunk programs dispatched, by their width in "
                           "rows", width=str(w))
            for w in filter(None, (self.prefill_chunk, self.narrow_chunk))}

    def _store_pages(self, shapes: dict, page_len: int) -> int:
        """Pages of the prefix store this engine keeps beside its strips,
        the NULL page among them; 0: no store. THE one rule, read off the
        configuration: a Llama block on the contiguous layout with its
        cache in the model's dtype and admission on the serial path.
        Absent for every other block (their caches are latents, states and
        rings), for ``kv_layout: paged`` (it shares pages in place), and
        for an int8 cache, ``dp_size > 1``, ``mixed_dispatch``,
        ``overlap``, speculative decoding and adapter packs, none of which
        is wired to it. ``inference.kv_store_pages`` sizes it: a negative
        value turns it off, a positive one is the pool's pages as given,
        0 (auto) holds twice the strips' rows (a document asked again
        later has to outlive the requests in between), fewer where the
        device could not hold them beside the weights, the strips and an
        eighth of its memory left to the programs, none where not a page
        fits: such a configuration starts as it did without a store."""
        inf, m = self.cfg.inference, self.cfg.model
        if (inf.kv_store_pages < 0 or m.model_type != "llama"
                or self.paged is not None or self.quantized
                or self.dp_size > 1 or self.mixed or self.overlap
                or self.spec_len > 0 or self.adapters is not None):
            return 0
        if inf.kv_store_pages:
            return max(int(inf.kv_store_pages), 2)
        auto = 2 * self.slots * -(-self.max_seq_len // page_len)
        limit = self._device_limit()
        if limit is None:  # a backend that does not say (the CPU)
            return 1 + auto
        tp = self.topo.tp_size
        like = jax.eval_shape(partial(llama.init_params, m=m),
                              jax.random.key(0))
        if self.quant_weights:
            like = jax.eval_shape(llama.quantize_params, like)
        # a device's share: the matrices, the strips and the pool all
        # split their heads over 'tp'
        page = kv_cache.cache_bytes(jax.eval_shape(
            partial(kv_cache.init_store, shapes, 1, page_len)))
        room = limit - limit // 8 - (llama.param_bytes(like)
                                     + self.kv_cache_bytes) // tp
        fit = min(auto, max(room, 0) // (page // tp))
        return 1 + fit if fit else 0

    def _device_limit(self) -> Optional[int]:
        """Bytes one device of the mesh may hold, where the backend says
        (the CPU does not, nor does a device described and not attached)."""
        try:
            stats = self.topo.mesh.local_devices[0].memory_stats()
        except (jax.errors.JaxRuntimeError, IndexError):
            return None
        return (stats or {}).get("bytes_limit")

    def _build_programs(self) -> None:
        """(Re)build the compiled model programs. Runs at construction and
        again when the flash->dense degradation path flips ``attend_impl``:
        the kernel choice is a trace-time constant the jit wrappers close
        over, so changing it means new programs, not a runtime branch."""
        # one-shot prefill runs B=1 fully replicated across dp (every shard
        # computes the same slice; only the owner's insert consumes it), so
        # its kv output specs come from the dp-FREE base — identical to
        # self._cspecs when dp == 1
        base_cspecs = (paged_kv.cache_pspecs(self.quantized,
                                             policy=self.page_policy)
                       if self.paged is not None
                       else self.model.cache_pspecs(self.cfg.model,
                                                    self.quantized))
        kv_spec = {n: s for n, s in base_cspecs.items()
                   if n not in paged_kv.META_LEAVES}
        mesh = self.topo.mesh
        # per-slot [B, ...] operands/outputs shard over dp (slot-major:
        # shard s owns global slots [s*spb, (s+1)*spb)); everything else
        # stays replicated. dp == 1 collapses dpP to P() — byte-identical
        # specs to the tp-only engine.
        dpP = P("dp") if self.dp_size > 1 else P()

        # the on-device sampling epilogue changes the programs' I/O: the
        # prefill family gains (key, temperature, top_k, top_p) inputs and
        # returns a sampled token id [1] where the host path returns [1, V]
        # logits; decode_step stops returning its [B, V] logits at all —
        # the whole point is that they never leave the device
        sod = self.sample_on_device
        samp = (P(), P(), P(), P()) if sod else ()
        # the return_hidden hook grows every program family by one
        # replicated [*, H] output (the residual stream is tp-replicated
        # after each layer's reduce) — a trace-time choice like the
        # sampling epilogue, so hidden-less engines compile byte-identical
        # programs
        hid = (P(),) if self.return_hidden else ()
        hidB = (dpP,) if self.return_hidden else ()
        # a block that counts appends its layers' counters, last of all
        st = (P(),) if self._n_stats else ()
        self._prefill_jit = jax.jit(shard_map(
            self._prefill_impl, mesh,
            in_specs=(self._dispatch_pspecs, P(), P()) + samp,
            out_specs=(kv_spec, P()) + hid + st))
        self._prefill_chunk_jit = jax.jit(shard_map(
            self._prefill_chunk_impl, mesh,
            in_specs=(self._dispatch_pspecs, self._cspecs,
                      P(), P(), P(), P()) + samp,
            out_specs=(self._cspecs, P()) + hid + st),
            donate_argnums=(1,))
        self._decode_jit = jax.jit(shard_map(
            self._decode_impl, mesh,
            in_specs=(self._decode_dispatch_pspecs, self._cspecs,
                      dpP, P(), dpP, dpP, dpP),
            out_specs=((self._cspecs, dpP) if sod
                       else (self._cspecs, dpP, dpP)) + hidB + st),
            donate_argnums=(1,))
        # the round programs (decode block, verify) are built where they
        # are first asked for (``_program``); a rebuild starts the table anew
        self._programs: dict = {}

    def _round_fields(self, kind: str) -> tuple:
        """The names of what a round program (``"decode_block"`` |
        ``"verify"`` | ``"blocks"``) returns, in order: THE one place that
        knows which outputs this engine's options add. The bodies return
        them so, the builder derives ``out_specs`` from them, and the host
        reads them back into a ``RoundResult`` by name."""
        rh = self.return_hidden
        # "packed": tokens, counts (and a verify's accepted), what the host
        # reads every round, as ONE int32 array (``_round_outputs``)
        return (("cache", "packed")
                # the post-round last token the overlap pipeline carries
                + (("next_tok",) if self.key_schedule == "slot" else ())
                + (("hidden",) if rh else ())
                + (("lane_out",) if self.mixed else ())
                + (("lane_hidden",) if self.mixed and rh else ())
                # a block that counts appends its counters, last of all
                + (("stats",) if kind == "blocks" or (
                    self._n_stats and kind == "decode_block") else ()))

    def _program(self, kind: str, poison: bool = False,
                 dev_tokens: bool = False):
        """The round program to run: ``kind`` is ``"decode_block"``,
        ``"verify"`` or ``"blocks"``, the one body of each under every key
        schedule, with
        the fused prefill lane on a mixed engine. Its operands after
        ``params`` and ``cache`` are what ``_round_operands`` makes: the
        round's host rows as ONE packed int32 array [rows, slots], split
        on its slot axis; ``tokens`` as an operand of its own only where
        the caller holds it on the device (``dev_tokens``: a program's
        arity is fixed, so the table's key says which); the keys; the
        lane's operands. Built on first use and kept; ``poison`` (chaos
        only) is a trace-time build of the same body, compiled only when a
        hook asks for it."""
        prog = self._programs.get((kind, poison, dev_tokens))
        if prog is None:
            dpP = P("dp") if self.dp_size > 1 else P()
            pack = P(None, "dp") if self.dp_size > 1 else P()
            # [B, 2] bases shard with their slots, the round's key (rows)
            # is replicated
            keys = dpP if self.key_schedule == "slot" else P()
            # the prefill lane's operands (``_lane_args``), every one a
            # per-shard [dp, ...] row set: tokens, slot, start, valid[,
            # key, temperature, top_k, top_p][, adapter]
            lane = (dpP,) * (4 + 4 * self.sample_on_device
                             + (self.adapters is not None)
                             if self.mixed else 0)
            impl = {"decode_block": self._decode_block_impl,
                    "verify": self._verify_impl,
                    "blocks": self._blocks_impl}[kind]
            prog = self._programs[kind, poison, dev_tokens] = jax.jit(
                shard_map(
                    partial(impl, poison=poison, dev_tokens=dev_tokens),
                    self.topo.mesh,
                    in_specs=(self._decode_dispatch_pspecs, self._cspecs,
                              pack)
                    + (dpP,) * dev_tokens + (keys,) + lane,
                    out_specs=tuple(
                        self._cspecs if n == "cache"
                        else P() if n == "stats" else dpP
                        for n in self._round_fields(kind))),
                donate_argnums=(1,))
        return prog

    # ---- dispatch hooks + graceful degradation ----------------------------

    def _hook(self, kind: str, budget=None) -> None:
        """Fire the before-dispatch hook with the active slot indices
        (``budget > 0`` rows; dispatches without a budget report none)
        and count the dispatch in the metrics registry. When a
        ClusterMonitor is attached (``attach_monitor`` — multi-host dp
        serving), every dispatch first checks peer leases: a dead dp peer
        means the collective about to run would wedge forever, so the
        monitor's exit path fires instead (exit 77 under the default
        exit_fn — the supervisor's restart signal)."""
        self._check_monitor()
        self.obs.registry.counter(
            "picotron_dispatch_total",
            "engine dispatches by kind", kind=kind).inc()
        if self.hooks is None:
            return
        slots = ([] if budget is None
                 else np.flatnonzero(np.asarray(budget) > 0).tolist())
        self.hooks.before_dispatch(kind, slots)

    def attach_monitor(self, monitor) -> None:
        """Attach a ``resilience.cluster.ClusterMonitor`` lease guard:
        every subsequent dispatch (and every migration's donating write)
        first checks peer leases, so a dead dp peer takes the monitor's
        exit path — exit 77 under the default exit_fn — instead of
        wedging this host inside the dispatch collective forever."""
        self.monitor = monitor

    def _check_monitor(self) -> None:
        if self.monitor is not None:
            dead = self.monitor.check_peers()
            if dead is not None:
                self.monitor._exit(*dead)

    def observe_dispatch(self, kind: str, seconds: float) -> None:
        """Record one dispatch's end-to-end wall time (submit through the
        caller's host sync) into the registry. Callers that pay the sync
        — the batcher's round closures, the benches — report here; the
        engine itself never blocks on its own async dispatches just to
        time them."""
        self.obs.registry.histogram(
            "picotron_dispatch_seconds",
            "dispatch wall time incl. host sync, by kind",
            kind=kind).observe(seconds)

    def _poison(self, kind: str) -> bool:
        return self.hooks is not None and self.hooks.poison_logits(kind)

    def _flash_fallback(self, exc: Exception) -> bool:
        """Degrade flash->dense after a failed dispatch: latch the process
        flag, log once, rebuild the compiled programs on dense. Returns
        whether the caller should re-dispatch."""
        impl = self.attend_impl
        if not (_runs_flash(impl) and self.cfg.inference.attend_fallback):
            return False
        global _FLASH_BROKEN
        if not _FLASH_BROKEN:
            _FLASH_BROKEN = True
            log0(f"attend_impl {impl!r} failed at dispatch "
                 f"({type(exc).__name__}: {exc}); falling back to 'dense' "
                 f"for the rest of the process", flush=True)
        self.attend_impl = self.cfg.inference.attend_impl = "dense"
        self._build_programs()
        return True

    def _dispatch(self, call):
        """Run one compiled cache dispatch. A flash failure rebuilds on
        dense and re-dispatches once (``call`` must re-read the jit
        attribute, not capture the object). The re-dispatch is sound when
        the failure predates execution (trace/compile — where flash breaks
        off-TPU); a failure AFTER the donated cache was consumed makes the
        retry fail fast on the deleted buffers, which lands in the
        batcher's slot-recovery path instead of wedging."""
        try:
            return call()
        except Exception as e:  # noqa: BLE001 - rethrown unless degrading
            if self._flash_fallback(e):
                return call()
            raise

    # ---- model programs (run inside shard_map; tp axis collectives live) --

    def _pack_kv(self, K, V):
        """Prefill K/V blocks in cache storage form: quantize (int8 mode)
        or cast to the cache dtype. hot_bf16 policy engines pack BOTH
        representations (full precision + int8 with scales) — the paged
        insert parks them side by side, the per-page flag picks the read.
        K and V leave as the cache's rows lie: ``kv_pack`` heads a row."""
        pack = partial(kv_cache.pack_heads, p=self.kv_pack)
        if self.quantized:
            qk, ks = kv_cache.quantize_kv(K)
            qv, vs = kv_cache.quantize_kv(V)
            return {"k": pack(qk), "v": pack(qv),
                    "k_scale": ks, "v_scale": vs}
        out = {"k": pack(K.astype(self.cache_dtype)),
               "v": pack(V.astype(self.cache_dtype))}
        if self.page_policy:
            qk, ks = kv_cache.quantize_kv(K)
            qv, vs = kv_cache.quantize_kv(V)
            out.update({"k_q": qk, "v_q": qv, "k_scale": ks, "v_scale": vs})
        return out

    def _epilogue(self, logits, key, temperature, top_k, top_p):
        """The fused on-device sampling epilogue: sanitize -> temperature
        -> top-k -> top-p -> categorical (sampling.sample's fused filter,
        exactly the host sampler's pipeline over the same key), collapsing
        the dispatch's host-bound payload from [B, V] fp32 logits to [B]
        int32 token ids."""
        return sampling.sample(logits, key, temperature, top_k, top_p)

    def _embed(self, params, tokens):
        """``tokens`` into the residual stream as the block does it (the
        seam's ``embed_lookup``, handed the config as ``head_logits`` is),
        in the model's dtype."""
        return self.model.embed_lookup(params["embed"], tokens,
                                       cfg=self.cfg).astype(self._dt)

    def _prefill_impl(self, params, tokens, length, *sample):
        """tokens [1, S_bucket] int32, length [1] -> (kv blocks, last-token
        logits [1, V]). Pad tokens beyond ``length`` produce K/V rows the
        length mask makes unreachable. With the on-device sampling
        epilogue, ``sample`` is (key, temperature [1], top_k [1],
        top_p [1]) and the second return is the sampled token id [1]
        int32 — the logits never leave the device."""
        cfg = self.cfg
        S = tokens.shape[1]
        cos_l = lax.dynamic_slice_in_dim(self._cos, 0, S, 0)
        sin_l = lax.dynamic_slice_in_dim(self._sin, 0, S, 0)
        h = self._embed(params, tokens)
        if self._n_stats:
            live = jnp.arange(S, dtype=jnp.int32)[None, :] < length[:, None]
            h, kv, stats = self._prefill_groups(params, h, cos_l, sin_l,
                                                live)
        else:

            def body(hc, lp):
                hc, kv = llama.decoder_layer(lp, hc, cos_l, sin_l, cfg,
                                             return_kv=True)
                return hc, kv

            h, (K, V) = lax.scan(body, h, params["layers"])
            kv, stats = self._pack_kv(K, V), None
        # only the last real token's logits are consumed: slice its hidden
        # row BEFORE the LM-head matmul and the vocab tp-gather, so the
        # bucket pays one [1, H] @ [H, V] row instead of S_bucket of them
        h_last = jnp.take_along_axis(h, (length - 1)[:, None, None], axis=1)
        last = tp_gather(self.model.head_logits(params, h_last, cfg))[:, 0]
        last = last.astype(jnp.float32)
        out = self._epilogue(last, *sample) if self.sample_on_device \
            else last
        out = (kv, out, h_last[:, 0]) if self.return_hidden else (kv, out)
        return out if stats is None else out + (stats,)

    def _prefill_groups(self, params, h, cos_l, sin_l, live):
        """The one-shot prefill of a block that counts: its groups of
        layers scanned one after the other over the whole sequence, each
        layer returning the rows it would write to a cache (of a recurrent
        layer: the state it would leave) and its stats. (h, each cache
        leaf's block stacked over the layers that have it, in the leaf's
        dtype, the stats [layers, counters])."""
        rows, stats = [], []
        for name, layer_fn, count in self.model.layer_groups(self.cfg.model):
            xs, whole = self._group_xs(params[name], count)

            def body(hc, lp):
                return layer_fn({**lp, **whole}, hc, cos_l, sin_l, self.cfg,
                                return_kv=True, live=live)

            h, out = lax.scan(body, h, xs)
            stats.append(out.pop(models.STATS))
            rows.append(out)
        # a leaf runs over the layers of the groups that have it, in order
        kv = {n: jnp.concatenate([r[n] for r in rows if n in r]).astype(dt)
              for n, dt in self._leaf_dtypes.items()}
        return h, kv, jnp.concatenate(stats)

    def _meta(self, cache) -> dict:
        """The layer-less host-owned metadata leaves a paged cache carries
        (block tables; page_quant under the hot_bf16 policy)."""
        return {n: cache[n] for n in ("block_tables", "page_quant")
                if n in cache}

    def _local_meta(self, cache) -> dict:
        """``_meta`` for use INSIDE a shard_map trace: with dp > 1 the page
        pool arrives shard-local (pages_per_shard pages, local page 0 =
        this shard's NULL page) while block tables carry GLOBAL page ids
        (shard s owns [s*pps, (s+1)*pps)), so subtract this shard's base —
        a slot's own entries localize into range, its NULL entries localize
        to 0. ``_rebuild`` keeps the ORIGINAL global tables; this view is
        read-only. dp == 1 is the identity."""
        meta = self._meta(cache)
        if self.dp_size > 1 and "block_tables" in meta:
            base = (lax.axis_index("dp").astype(jnp.int32)
                    * self.pages_per_shard)
            meta = {**meta, "block_tables": meta["block_tables"] - base}
        return meta

    def _slot_owner(self, slot):
        """Owner gating for single-slot programs under dp sharding: map a
        GLOBAL slot id to (local slot, is_owner) on the executing shard.
        Non-owner shards clip to a valid local index so slicing stays in
        bounds; their compute is discarded (writes where'd out, logits
        psum-masked). dp == 1 returns the slot unchanged with owner
        None (no gating)."""
        if self.dp_size <= 1:
            return slot, None
        shard = lax.axis_index("dp").astype(jnp.int32)
        loc = jnp.asarray(slot, jnp.int32) - shard * self.slots_per_shard
        is_owner = (loc >= 0) & (loc < self.slots_per_shard)
        return jnp.clip(loc, 0, self.slots_per_shard - 1), is_owner

    def _owner_reduce(self, x, owner):
        """Make a single-slot program output replicated across dp shards:
        the owner contributes its value, the rest contribute zeros, one
        psum agrees everywhere (where-select, not multiply, so non-owner
        garbage — even NaN — never reaches the sum). This is the ONLY dp
        collective in the serving programs, and it lives on the chunked
        prefill path alone; decode_block/verify stay collective-free.
        dp == 1 (owner None) is the identity."""
        if owner is None:
            return x
        return comm_trace.log(
            "prefill_owner_reduce", "dp",
            lax.psum(jnp.where(owner, x, jnp.zeros_like(x)), "dp"))

    def _slot_meta(self, cache, slot, gate) -> dict:
        """Addressing entries that point the layer scan at ONE (shard-
        local) slot — the B == 1 programs: a prefill chunk, the mixed
        lane. Contiguous: the slot index, and ``gate`` (None = always
        open) choosing row for row between the chunk's K/V and the bytes
        already there. Paged: the slot's block-table row, which a closed
        gate points at this shard's NULL scratch page."""
        if self.kv_layout != "paged":
            return {"slot": slot} if gate is None else {"slot": slot,
                                                        "gate": gate}
        meta = self._local_meta(cache)
        row = lax.dynamic_slice_in_dim(meta["block_tables"], slot, 1,
                                       axis=0)  # [1, max_pages]
        if gate is not None:
            row = jnp.where(gate, row, jnp.zeros_like(row))
        return {**meta, "block_tables": row}

    def _scan_layers(self, params, cache, h, cos_b, sin_b, pos, meta):
        """THE layer scan of every serving program: run ``h`` through the
        model's groups of stacked layers (``layer_groups``: one for the
        Llama block; dense layers, then expert layers; the runs of a
        per-layer pattern), one scan after the other against the one
        cache, return (h, updated stacked leaves).
        Of a block that counts, the returned dict also holds the stats
        [layers, counters] (``models.STATS``: not a leaf; the programs
        take it out before they rebuild the cache; a layer's own row, so
        that no int32 sums over layers). The [L, ...] cache leaves ride the scan's CARRY beside
        the residual stream and what the scan iterates over is the
        stacked params and the layer INDEX — nothing cache-shaped is a
        scan input or output, so XLA keeps the cache in the one buffer
        the program was donated: kv_cache.cache_write scatters the new
        rows into it at ``[layer, ...]`` and kv_cache.attend reads the
        layer through the index. ``meta`` holds the per-dispatch
        addressing entries (paged tables, ``draft_valid``, ``slot`` /
        ``gate``) spliced into the dict each layer sees; they are not
        leaves and never enter the carry."""
        leaves = {n: a for n, a in cache.items()
                  if n not in paged_kv.META_LEAVES}
        first, stats = 0, None
        for name, layer_fn, count in self.model.layer_groups(self.cfg.model):
            index = jnp.arange(first, first + count, dtype=jnp.int32)
            stack, whole = self._group_xs(params[name], count)

            def body(carry, xs):
                hc, lv = carry
                lp, layer = xs
                hc, out = layer_fn({**lp, **whole}, hc, cos_b, sin_b,
                                   self.cfg, cache={**lv, **meta}, pos=pos,
                                   layer=layer)
                # what a block that counts counted in this layer
                return (hc, {n: out[n] for n in lv}), out.get(models.STATS)

            (h, leaves), counted = lax.scan(body, (h, leaves),
                                            (stack, index))
            if counted is not None:
                stats = counted if stats is None else jnp.concatenate(
                    [stats, counted])
            first += count
        if stats is not None:
            leaves[models.STATS] = stats
        return h, leaves

    def _group_xs(self, stack, count: int) -> tuple:
        """(what a scan over a group of ``count`` stacked layers iterates,
        the leaves its layers are handed whole): the model's ``UNSLICED``
        leaves stay out of the scan's slices, and the layer finds its own
        row of them under ``"row"``. A block without such leaves scans its
        stack as it is."""
        whole = {n: stack[n] for n in self.model.UNSLICED if n in stack}
        if not whole:
            return stack, {}
        xs = {n: v for n, v in stack.items() if n not in whole}
        xs["row"] = jnp.arange(count, dtype=jnp.int32)
        return xs, whole

    def _rebuild(self, cache, new_leaves, lengths):
        """Reassemble a cache pytree from the layer scan's updated stacked
        leaves + lengths, carrying the paged layout's metadata leaves
        through unchanged (the HOST allocator owns them; device programs
        only read)."""
        return {**new_leaves, **self._meta(cache), "lengths": lengths}

    def _model_block(self, params, cache, tokens, rows, pos,
                     extra_meta=None, head: bool = True):
        """The shared incremental-decode model body: embed ``tokens``
        [B, S] at RoPE positions ``rows`` [B, S], scan the layer stack
        writing each slot's S new K/V rows from ``pos`` [B]
        (kv_cache.cache_write), attend causally over cache prefix + block,
        and return (updated stacked leaves, logits [B, S, V] fp32,
        pre-final-norm hidden states [B, S, H]). S == 1 is the decode
        step; S > 1 the speculative verify block. ``extra_meta`` rides
        into each layer's cache dict alongside the paged metadata (the
        ragged verify's ``draft_valid`` write mask; a blocks round's own
        ``live`` rows). Without ``head`` the logits are None (a commit
        forward's are read by nobody; a fused forward runs the head itself,
        on half of its rows). Lengths are NOT
        advanced here — callers apply their own activity rule."""
        cos_b, sin_b = rope_at_positions(self._cos, self._sin, rows)
        h = self._embed(params, tokens)
        meta = {**self._local_meta(cache), **(extra_meta or {})}
        if self._n_stats and "live" not in meta:
            # free slots (length 0) ride along uncounted and unrouted
            meta["live"] = jnp.broadcast_to((pos > 0)[:, None], rows.shape)
        h, new_leaves = self._scan_layers(params, cache, h,
                                          cos_b, sin_b, pos, meta)
        if not head:
            return new_leaves, None, h
        logits = tp_gather(self.model.head_logits(params, h, self.cfg))
        return new_leaves, logits.astype(jnp.float32), h

    def _decode_core(self, params, cache, tokens, active=None):
        """One model step for all slots: ``tokens`` [B] at each slot's own
        ``cache['lengths']`` position -> (updated stacked leaves,
        logits [B, V] fp32, hidden [B, H]). ``active`` [B] (a decode
        block's: parked and in budget) reaches every layer as an
        ``"active"`` entry: a slot's ghost K/V row hides behind its
        length, so the blocks that keep K/V alone do not read it; a
        recurrent state must not advance."""
        pos = cache["lengths"]  # [B] write index of the incoming token
        new_leaves, logits, h = self._model_block(
            params, cache, tokens[:, None], pos[:, None], pos,
            extra_meta=None if active is None else {"active": active})
        return new_leaves, logits[:, 0], h[:, 0]

    def _decode_impl(self, params, cache, tokens, key, temperature,
                     top_k, top_p):
        """One autoregressive step for all slots: tokens [B] (each slot's
        current last token), cache lengths give every slot its position.
        Sampling always runs on device; with the epilogue enabled the
        [B, V] logits are additionally DROPPED from the outputs, so the
        dispatch's host payload is the [B] token ids alone. A
        ``return_hidden`` engine appends the step's pre-final-norm hidden
        states [B, H] — the learned drafter's input."""
        pos = cache["lengths"]
        new_leaves, logits, h = self._decode_core(params, cache, tokens)
        stats = new_leaves.pop(models.STATS, None)
        next_tok = sampling.sample(logits, key, temperature, top_k, top_p)
        # free slots (length 0) ride along for shape stability but stay at
        # length 0 — their row-0 writes are never visible
        new_cache = self._rebuild(cache, new_leaves,
                                  jnp.where(pos > 0, pos + 1, 0))
        out = ((new_cache, next_tok) if self.sample_on_device
               else (new_cache, next_tok, logits))
        out = out + (h,) if self.return_hidden else out
        return out if stats is None else out + (stats,)

    def _decode_block_impl(self, params, cache, pack, *rest, poison=False,
                           dev_tokens=False):
        """``decode_block_len`` autoregressive steps in one program, under
        every key schedule.

        ``pack`` and ``rest`` are ``_round_operands``'s (``_unpack_rows``):
        tokens [B] (each slot's current last token), eos_id [B] int32
        (−1 = none), budget [B] int32 remaining tokens. ``keys`` is the
        engine's ``key_schedule`` (a constant of the trace): ``"round"``
        takes [block_len, 2], one PRNG key per in-block step — the host's
        per-round split chain, so block_len == 1 reproduces the per-token
        loop bit-for-bit; ``"slot"`` takes the per-slot BASE keys [B, 2],
        and every row's draw at pre-step length ℓ uses
        ``fold_in(keys[b], ℓ)`` — the key that position owns no matter how
        steps are grouped into rounds, the invariant the overlap
        pipeline's bit-identity rests on (docs/INFERENCE.md "Overlapped
        scheduling").
        A slot is active while it has a parked sequence AND budget; hitting
        EOS zeroes its budget. Inactive slots emit pad token 0, stop
        advancing their cache length, and their (recomputed) row writes
        land beyond the length mask — invisible, exactly like the free
        slots that already ride through the single-step program.

        Returns ``_round_fields("decode_block")``: cache, packed (tokens
        [B, block_len] and counts [B] side by side: ``counts[b]`` leading
        entries of row b are the tokens slot b actually produced); under
        the slot schedule next_tok [B], the final carry token (each slot's
        post-block last token, the input token where a slot never ran),
        which the
        lookahead dispatch consumes without a host sync; on a
        ``return_hidden`` engine hidden [B, H], each slot's
        pre-final-norm hidden state at its LAST active step — the position
        whose logits produced the slot's final emitted token, exactly what
        the learned drafter needs to draft its continuation; on a mixed
        engine the lane's outputs (``_lane_chunk``, run on ``lane`` after
        the block: the lane slot rides the block inactive — budget 0, so
        its ghost row lands at its current length and the lane
        immediately overwrites it); of a block that counts, its counters.

        ``poison`` (trace-time, chaos only) replaces every step's logits
        with NaN — the build that proves the sampler's non-finite gate
        keeps emitting defined tokens, the exact counterpart of
        train_step's ``poison_nonfinite``."""
        (tokens, eos_id, budget, temperature, top_k, top_p), (keys, *lane) \
            = self._unpack_rows("decode_block", pack, rest, dev_tokens)
        rh = self.return_hidden
        by_slot = self.key_schedule == "slot"
        hid0 = jnp.zeros((tokens.shape[0], self.cfg.model.hidden_size),
                         self._dt)

        def step(carry, key_t):
            cache, tok, budget, hid = carry
            pos = cache["lengths"]
            active = (pos > 0) & (budget > 0)
            if by_slot:
                key_t = jax.vmap(jax.random.fold_in)(keys, pos)
            new_leaves, logits, h = self._decode_core(params, cache, tok,
                                                      active=active)
            # a block that counts: its step's stats leave with the tokens
            counted = ((new_leaves.pop(models.STATS),) if self._n_stats
                       else ())
            if poison:
                logits = jnp.full_like(logits, jnp.nan)
            sample = sampling.sample_rowkeys if by_slot else sampling.sample
            sampled = sample(logits, key_t, temperature, top_k, top_p)
            emit = jnp.where(active, sampled, 0)
            new_budget = jnp.where(active, budget - 1, budget)
            hit_eos = active & (eos_id >= 0) & (sampled == eos_id)
            new_budget = jnp.where(hit_eos, 0, new_budget)
            new_cache = self._rebuild(cache, new_leaves,
                                      jnp.where(active, pos + 1, pos))
            next_tok = jnp.where(active, sampled, tok)
            new_hid = jnp.where(active[:, None], h, hid) if rh else hid
            return (new_cache, next_tok, new_budget, new_hid), \
                (emit, active) + counted

        (cache, tok, _, hid), (toks, actives, *counted) = lax.scan(
            step, (cache, tokens, budget, hid0),
            None if by_slot else keys,
            length=self.decode_block_len if by_slot else None)
        out = {"cache": cache, "tokens": jnp.swapaxes(toks, 0, 1),
               "counts": jnp.sum(actives.astype(jnp.int32), axis=0),
               "next_tok": tok, "hidden": hid}
        if counted:
            out["stats"] = jnp.sum(counted[0], axis=0)
        return self._round_outputs("decode_block", params, out, lane)

    def _verify_impl(self, params, cache, pack, *rest, poison=False,
                     dev_tokens=False):
        """The speculative verify pass (``pack`` and ``rest`` are
        ``_round_operands``'s: ``_unpack_rows``): tokens [B, S] (S =
        spec_len + 1 — each slot's current last token followed by its
        spec_len drafted continuation tokens), scored in ONE model
        dispatch. ``valid`` [B]
        int32 is each slot's count of REAL fed tokens (its draft length
        + 1) — the RAGGED hook: the compiled shape stays [B, spec_len+1]
        while each slot speculates at its own controller-chosen length
        (pad columns past ``valid`` are forced rejections in the accept
        rule and masked out of the K/V write — kv_cache.cache_write's
        ``draft_valid``); ``valid == S`` everywhere reproduces the
        fixed-length verify bit for bit.

        All S positions embed at each slot's own offsets
        (``cache['lengths'] + 0..S-1``), their K/V are written into the
        slot OPTIMISTICALLY (the batched-write branch of
        kv_cache.cache_write; int8 caches quantize on write), and
        attention runs causally over the cache prefix plus the fed block —
        the same masked kernel the chunked prefill uses, batched over
        slots. The resulting logits[b, i] score the token FOLLOWING fed
        token i. Under the round schedule (``key`` one PRNG key)
        ``sampling.speculative_accept`` accepts the matching draft prefix
        and draws the one fresh token, all on device. Under the slot
        schedule (``key`` the per-slot base keys [B, 2]) acceptance is
        sample-and-match (``sampling.speculative_match``): the program
        draws the target chain's own token at every fed position with
        that position's folded key and accepts the matching draft prefix,
        so the emitted stream never depends on the draft VALUES and equals
        the per-position decode chain bit for bit (the property that lets
        the overlap pipeline verify against one-round-stale drafts).

        Rollback is the length pointer: ``lengths`` advances by the
        emitted count only (accepted prefix + the fresh token's slot-feed
        position), so rejected draft rows — already written — sit beyond
        the mask, stale and unreachable, and the next dispatch overwrites
        them. EOS truncates the emitted run on device (the stream ends AT
        the first emitted EOS); ``budget`` [B] caps it exactly like
        decode_block's budget. Free slots (length 0) ride along inactive:
        they emit count 0 and their length stays 0.

        Returns ``_round_fields("verify")``: cache, packed (side by
        side: tokens, the emitted run, [B, S]; counts [B]; accepted [B] —
        the number of DRAFT tokens that made it into the emitted stream,
        the accept-rate numerator);
        under the slot schedule next_tok [B], the last emitted token where
        the row ran, else the fed last token; on a ``return_hidden``
        engine hidden [B, H], each slot's pre-final-norm hidden state at
        the position whose logits produced its final emitted token (row
        ``counts - 1``) — the learned drafter's next input; on a mixed
        engine the lane's outputs, as ``_decode_block_impl`` appends them.
        """
        (tokens, valid, eos_id, budget, temperature, top_k, top_p), \
            (key, *lane) = self._unpack_rows("verify", pack, rest,
                                             dev_tokens)
        B, S = tokens.shape
        pos0 = cache["lengths"]
        rows = pos0[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        new_leaves, logits, h = self._model_block(
            params, cache, tokens, rows, pos0,
            extra_meta={"draft_valid": valid})  # logits [B, S, V]
        if poison:
            # chaos only (trace-time): the build that proves the accept
            # rule's sanitized argmax keeps the emitted stream defined —
            # decode_block's ``poison`` counterpart
            logits = jnp.full_like(logits, jnp.nan)
        if self.key_schedule == "slot":
            # rows[b, i] is exactly the fold_in data the non-speculative
            # chain uses for the token following fed token i (its
            # pre-step length)
            emitted, counts = sampling.speculative_match(
                logits, tokens[:, 1:], key, rows, temperature, top_k,
                top_p, draft_len=valid - 1)
        else:
            emitted, counts = sampling.speculative_accept(
                logits, tokens[:, 1:], key, temperature, top_k, top_p,
                draft_len=valid - 1)
        raw = counts  # pre-clip: accepted drafts + 1 fresh token
        active = (pos0 > 0) & (budget > 0)
        counts = jnp.where(active, jnp.minimum(counts, budget), 0)
        cols = jnp.arange(S, dtype=jnp.int32)[None, :]
        is_eos = ((eos_id >= 0)[:, None] & (emitted == eos_id[:, None])
                  & (cols < counts[:, None]))
        counts = jnp.where(jnp.any(is_eos, axis=1),
                           jnp.argmax(is_eos, axis=1) + 1, counts)
        emitted = jnp.where(cols < counts[:, None], emitted, 0)
        # of the emitted run, all but (possibly) the last token are drafts:
        # when nothing clipped, raw - 1 drafts + 1 fresh; when EOS/budget
        # clipped below that, every emitted token was a draft
        out = {"tokens": emitted, "counts": counts,
               "accepted": jnp.minimum(raw - 1, counts),
               "cache": self._rebuild(
                   cache, new_leaves,
                   jnp.where(active, pos0 + counts, pos0))}
        # the last emitted token (greedy: == argmax over this row's
        # logits) came from row counts - 1; clip covers inactive rows
        last = jnp.clip(counts - 1, 0, S - 1)
        if self.key_schedule == "slot":
            out["next_tok"] = jnp.where(
                counts > 0,
                jnp.take_along_axis(emitted, last[:, None], axis=1)[:, 0],
                tokens[:, 0])
        if self.return_hidden:
            out["hidden"] = jnp.take_along_axis(
                h, last[:, None, None], axis=1)[:, 0]
        return self._round_outputs("verify", params, out, lane)

    def _block_forward(self, params, cache, x, active, head: bool = True):
        """One forward of every slot's current block: ``x`` [B,
        block_length] embedded at positions ``lengths .. lengths +
        block_length``, its K/V rows written there and then attended (each
        row sees the slot's stored prefix and the whole block), ``lengths``
        NOT advanced: the rows are provisional, beyond the length for every
        later reader, and the next forward of the slot overwrites them. A
        round runs it for a block's denoise forwards behind the first
        (``_fused_forward`` is the first); ``_commit`` is this forward of a
        finished block, then the advance. Slots that are not ``active`` [B]
        ride along unrouted and uncounted. Returns (cache, logits [B,
        block_length, V] float32 or None without ``head``, the layers'
        stats)."""
        pos = cache["lengths"]
        Bd = x.shape[1]
        rows = pos[:, None] + jnp.arange(Bd, dtype=jnp.int32)[None, :]
        new_leaves, logits, _ = self._model_block(
            params, cache, x, rows, pos, head=head, extra_meta={
                "live": jnp.broadcast_to(active[:, None], rows.shape)})
        stats = self._pop_stats(new_leaves)
        return self._rebuild(cache, new_leaves, pos), logits, stats

    def _pop_stats(self, new_leaves) -> jnp.ndarray:
        """What the layers of a forward counted, taken out of its leaves."""
        return new_leaves.pop(models.STATS, jnp.zeros(
            (self.cfg.model.num_hidden_layers, 0), jnp.int32))

    def _fused_forward(self, params, cache, done, x, owed, active):
        """The first denoise forward of a block with the commit of the
        block before it inside: ``2 x block_length`` rows a slot at
        positions ``lengths .. lengths + 2 x block_length``, all written and
        then attended under the model's own band (``kv_cache.attend``: the
        first ``block_length`` rows see the stored prefix and themselves,
        the rest those and themselves), the keys read once for both halves.

        Where a slot is ``owed`` [B] (a finished block ``done`` [B,
        block_length] of it waits for its K/V, and the slot is ``active``)
        the halves are ``done``, then ``x``, the block that starts: the
        first half's rows are the rows a commit forward of ``done`` writes
        (the same tokens at the same positions behind the same prefix), the
        slot's length passes them, and ``x`` has been forwarded behind them
        as its denoise forward is. Where nothing is owed (a slot's first
        block; a slot that is not active) ``x`` rides in the FIRST half, at
        the length where it belongs, the length stays, and the second half
        is ``x`` once more, not live, its rows provisional beyond the block
        (past the window they drop). The head runs on one half's rows a
        slot: the half that holds ``x``. Returns (cache, logits [B,
        block_length, V] float32 of ``x``, the layers' stats)."""
        pos = cache["lengths"]
        Bd = x.shape[1]
        tokens = jnp.concatenate(
            [jnp.where(owed[:, None], done, x), x], axis=1)
        rows = pos[:, None] + jnp.arange(2 * Bd, dtype=jnp.int32)[None, :]
        live = jnp.repeat(jnp.stack([active, owed], axis=1), Bd, axis=1)
        new_leaves, _, h = self._model_block(
            params, cache, tokens, rows, pos, head=False,
            extra_meta={"live": live})
        stats = self._pop_stats(new_leaves)
        h = jnp.where(owed[:, None, None], h[:, Bd:], h[:, :Bd])
        logits = tp_gather(self.model.head_logits(params, h, self.cfg))
        return (self._rebuild(cache, new_leaves,
                              jnp.where(owed, pos + Bd, pos)),
                logits.astype(jnp.float32), stats)

    def _commit(self, params, cache, x, active):
        """The commit forward of a finished block ``x`` alone: its K/V stay,
        and the ``active`` slots' lengths pass them. (cache, stats). A round
        commits inside the next block's first forward (``_fused_forward``);
        this is ``block_forward(commit=True)``'s, for the checks that run a
        round's forwards one at a time and for a caller that wants a
        waiting block stored without starting another."""
        with jax.named_scope("diffusion/commit"):
            cache, _, stats = self._block_forward(params, cache, x, active,
                                                  head=False)
        pos = cache["lengths"]
        return {**cache, "lengths": jnp.where(
            active, pos + x.shape[1], pos)}, stats

    def _blocks_impl(self, params, cache, pack, *rest, poison=False,
                     dev_tokens=False):
        """A round of generation by diffusion over blocks (SDAR's published
        ``block_diffusion_generate``, a cache under it): ``decode_block_len /
        block_length`` blocks a slot, one after the other, in one program.

        ``pack`` and ``rest`` are ``_round_operands``'s: tokens [B, 2 x
        block_length] and given [B], eos_id, budget, the sampling rows, and
        the round's keys [decode_block_len, 2] (block ``j``'s denoise step
        ``s`` draws with ``keys[j * block_length + s]``). The tokens' second
        half holds the leading positions of each slot's FIRST block of the
        round that are given, ``given`` of them (a prompt's remainder behind
        its whole prefilled blocks; 0 in every later round); their first
        half is the slot's WAITING block, the last block of its last round,
        whose tokens are final and streamed and whose K/V are not stored
        yet (-1 throughout: nothing waits).

        A block starts as its given positions and ``mask_token_id`` behind
        them; masked-ness is a flag a position, never an id compared. Its
        first denoise forward carries the finished block before it in
        front (``_fused_forward``: the waiting block in a round's first
        block, the round's own last one after; a slot's length passes that
        block there, and a slot with none rides the same forward with its
        block alone). While a position of a live slot is still masked, up
        to ``denoising_steps`` steps in all: one more denoise forward of
        every slot's block (``_block_forward``: written, attended, not
        counted in ``lengths``). Behind every denoise forward a draw at
        every position (``sampling.sample``: the argmax at temperature 0),
        and the confidence rule (``sampling.confidence_unmask``) fixes some
        of the masked positions at their draw. No forward commits a block
        alone: a round of two blocks is two fused forwards and six plain
        ones where four denoising steps fix a block. The block's new tokens
        (behind the given ones) are emitted up to the budget and the first
        EOS among them, which end the slot's stream: it rides the round's
        later blocks inactive (its provisional rows land beyond its length),
        and its last block is never stored, since nothing reads it. The
        round's last block WAITS: its rows in the cache are provisional, the
        slot's length stops in front of them, and the caller hands its
        tokens back with the slot's next round (``decode_block``'s
        ``waiting``; the batcher keeps them), whose first forward stores
        them. A slot is live while its budget lasts; a free slot's is 0.

        Returns ``_round_fields("blocks")``: cache, packed (tokens [B,
        decode_block_len], a slot's emitted run left-packed, and counts [B]
        side by side), and the stats in the order of ``stat_names``, one
        vector: the layers' own summed over the forwards and the layers, the
        round's ``sampling.DIFFUSION_STATS`` behind them (a fused forward
        counts as one forward of ``kind="fused"``, the blocks it commits as
        blocks, and ``block_length`` rows a live slot: the rows that can
        gain a token)."""
        (tokens, given, eos_id, budget, temperature, top_k, top_p), \
            (keys, *lane) = self._unpack_rows("blocks", pack, rest,
                                              dev_tokens)
        m = self.cfg.model
        Bd, T = m.block_length, m.denoising_steps
        B, nb = tokens.shape[0], self.decode_block_len // Bd
        waiting, tokens = tokens[:, :Bd], tokens[:, Bd:]
        cols = jnp.arange(Bd, dtype=jnp.int32)[None, :]
        per_row = lambda a: jnp.repeat(a, Bd)

        def block(carry, keys_j):
            cache, given, budget, stats, counts, done, was_live = carry
            active = budget > 0
            owed = was_live & active
            x = jnp.where(cols < given[:, None], tokens, m.mask_token_id)
            masked = (cols >= given[:, None]) & active[:, None]
            n_live = jnp.sum(active, dtype=jnp.int32)

            def unmask(c, forward, kind, blocks=0):
                """A denoise forward's draw and the rule, on the loop's
                state ``c``; ``forward`` is what the forward of ``kind``
                returned, ``blocks`` how many blocks it committed."""
                s, _, x, masked, stats, counts = c
                cache, logits, counted = forward
                if poison:
                    logits = jnp.full_like(logits, jnp.nan)
                with jax.named_scope("diffusion/unmask"):
                    x0 = sampling.sample(
                        logits.reshape(B * Bd, -1), keys_j[s],
                        per_row(temperature), per_row(top_k),
                        per_row(top_p)).reshape(B, Bd)
                    fix, passed = sampling.confidence_unmask(
                        logits, x0, masked,
                        sampling.transfer_count(Bd, T, s), m.remasking,
                        m.confidence_threshold)
                counts = counts + sampling.diffusion_counts(
                    kind, blocks=blocks, positions_unmasked=jnp.sum(fix),
                    threshold_passes=jnp.sum(passed), rows=Bd * n_live)
                return (s + 1, cache, jnp.where(fix, x0, x), masked & ~fix,
                        stats + counted, counts)

            with jax.named_scope("diffusion/fused"):
                fused = self._fused_forward(params, cache, done, x, owed,
                                            active)
            _, cache, x, _, stats, counts = lax.while_loop(
                lambda c: (c[0] < T) & jnp.any(c[3]),
                lambda c: unmask(c, self._block_forward(
                    params, c[1], c[2], active), "denoise"),
                unmask((jnp.zeros((), jnp.int32), cache, x, masked, stats,
                        counts), fused, "fused", jnp.sum(owed)))
            # the block's new tokens, up to the budget and the first EOS
            n = jnp.where(active, jnp.minimum(Bd - given, budget), 0)
            new = (cols >= given[:, None]) & (cols < (given + n)[:, None])
            is_eos = new & (eos_id >= 0)[:, None] & (x == eos_id[:, None])
            hit = jnp.any(is_eos, axis=1)
            n = jnp.where(hit, jnp.argmax(is_eos, axis=1) - given + 1, n)
            new &= cols < (given + n)[:, None]
            budget = jnp.where(hit, 0, budget - n)
            return (cache, jnp.zeros_like(given), budget, stats, counts, x,
                    active), (jnp.where(new, x, 0), n)

        zeros = lambda *shape: jnp.zeros(shape, jnp.int32)
        (cache, _, _, stats, counts, _, _), (toks, ns) = lax.scan(
            block, (cache, given, budget,
                    zeros(m.num_hidden_layers, self._n_stats),
                    zeros(len(sampling.DIFFUSION_STATS)),
                    waiting, waiting[:, 0] >= 0),
            keys.reshape(nb, Bd, 2))
        # a slot's run lies behind its given positions, blocks end to end
        flat = jnp.swapaxes(toks, 0, 1).reshape(B, nb * Bd)
        total = jnp.sum(ns, axis=0)
        at = jnp.arange(nb * Bd, dtype=jnp.int32)[None, :]
        run = jnp.take_along_axis(
            flat, jnp.minimum(at + given[:, None], nb * Bd - 1), axis=1)
        out = {"cache": cache, "counts": total,
               "tokens": jnp.where(at < total[:, None], run, 0),
               "stats": jnp.concatenate([jnp.sum(stats, axis=0), counts])}
        return self._round_outputs("blocks", params, out, lane)

    def _block_forward_impl(self, params, cache, tokens, active, *,
                            commit: bool):
        """``_block_forward`` (or ``_commit``) as a program of its own
        (``block_forward``): a round's forwards one at a time, for the
        checks that read every forward's logits."""
        if commit:
            cache, stats = self._commit(params, cache, tokens, active)
            return cache, jnp.zeros((), jnp.float32), stats
        return self._block_forward(params, cache, tokens, active)

    def _unpack_rows(self, kind: str, pack, rest, dev_tokens: bool):
        """A round body's first lines: ``_round_operands``'s packed int32
        rows [rows, B] back under their names, ``(tokens[, valid], eos_id,
        budget, temperature, top_k, top_p)``, and the operands left over
        (the keys, then the lane's). The float rows rode as their bits and
        are bit-cast back, so a sampled token is what separate float32
        operands gave. ``tokens`` is the leading row (a verify's leading
        S, one a fed position; a blocks round's leading ``2 x
        block_length``, the waiting block and the next block's positions,
        with how many of these are given as its ``valid``) unless the caller
        held it on the device: then it is the first of ``rest``, as it was
        handed in."""
        n = len(_PACK_ROWS)
        lead, (eos_id, budget, top_k, temperature, top_p) = \
            pack[:-n], pack[-n:]
        temperature, top_p = (lax.bitcast_convert_type(r, jnp.float32)
                              for r in (temperature, top_p))
        valid = ()
        if kind != "decode_block":
            lead, valid = lead[:-1], (lead[-1],)
        if dev_tokens:
            tokens, *rest = rest
        else:
            tokens = lead[0] if kind == "decode_block" else lead.T
        return (tokens, *valid, eos_id, budget, temperature, top_k,
                top_p), rest

    def _round_outputs(self, kind: str, params, out: dict, lane) -> tuple:
        """A round program's tail: on a mixed engine the fused prefill
        lane, run on the cache the round just updated; what the host
        reads every round (``RoundResult.host``) side by side in one int32
        array; then what the program returns, in ``_round_fields``'s
        order."""
        out["packed"] = jnp.concatenate(
            [out["tokens"], out["counts"][:, None]]
            + ([out["accepted"][:, None]] if kind == "verify" else []),
            axis=1).astype(jnp.int32)
        if self.mixed:
            out["cache"], out["lane_out"], out["lane_hidden"] = \
                self._lane_chunk(params, out["cache"], *lane)
        return tuple(out[n] for n in self._round_fields(kind))

    def _prefill_chunk_impl(self, params, cache, tokens, slot, start, valid,
                            *sample):
        """One fixed-width prefill chunk for one slot: tokens [1, C] (pad
        past ``valid``), written into the cache at rows
        [start, start + C) of ``slot``. Queries attend causally over the
        already-written prefix plus the chunk (decode_attention with
        S = C); pad queries' outputs and their K/V rows beyond
        ``start + valid`` sit past the final length — unreachable. Returns
        (cache with lengths[slot] = start + valid, the last valid token's
        logits [1, V] fp32 — consumed by the caller on the final chunk).
        With the on-device epilogue, ``sample`` is (key, temperature,
        top_k, top_p) and the second return is the sampled token [1]
        int32 instead — every chunk samples from the SAME key (cheap next
        to the model body) and only the final chunk's draw is consumed,
        so no key is ever burned on an intermediate chunk.

        Both layouts run this body (``_slot_meta`` is the difference): the
        contiguous cache is addressed at ``[layer, slot, start..]``, the
        paged pool through the slot's block-table row — which is also the
        prefix-sharing resume path: with ``start`` past a cached prefix,
        the chunk attends over SHARED pages it never computed."""
        cfg = self.cfg
        C = tokens.shape[1]
        start = jnp.asarray(start, jnp.int32)
        pos_rows = (start + jnp.arange(C, dtype=jnp.int32))[None, :]  # [1,C]
        cos_b, sin_b = rope_at_positions(self._cos, self._sin, pos_rows)
        h = self._embed(params, tokens)
        lengths = cache["lengths"]
        pos = jnp.full((1,), start, jnp.int32)
        # dp > 1: every shard traces the same chunk, but only the slot's
        # owner keeps its writes — non-owners address a clipped local
        # slot and write its own bytes back row for row (contiguous), or
        # scribble their NULL scratch page (paged); their reads never
        # feed the result (logits psum-masked below, lengths untouched)
        loc, owner = self._slot_owner(slot)
        meta = self._slot_meta(cache, loc, owner)
        if self._n_stats:
            # the chunk's pad rows are neither counted nor routed
            meta["live"] = (jnp.arange(C, dtype=jnp.int32) < valid)[None, :]
        h, new_leaves = self._scan_layers(params, cache, h, cos_b, sin_b,
                                          pos, meta)
        stats = new_leaves.pop(models.STATS, None)
        idx = jnp.clip(valid - 1, 0, C - 1)
        h_last = jnp.take_along_axis(
            h, jnp.full((1, 1, 1), idx, jnp.int32), axis=1)
        last = tp_gather(self.model.head_logits(params, h_last, cfg))[:, 0]
        last = self._owner_reduce(last.astype(jnp.float32), owner)
        new_lengths = lengths.at[loc].set(start + valid)
        if owner is not None:
            new_lengths = jnp.where(owner, new_lengths, lengths)
        new_cache = self._rebuild(cache, new_leaves, new_lengths)
        out = self._epilogue(last, *sample) if self.sample_on_device \
            else last
        out = ((new_cache, out, self._owner_reduce(h_last[:, 0], owner))
               if self.return_hidden else (new_cache, out))
        return out if stats is None else out + (stats,)

    def _lane_chunk(self, params, cache, tokens, slot, start, valid, *rest):
        """The fused prefill LANE: one fixed-width chunk for one slot per
        dp shard, run on the cache the SAME dispatch's decode half just
        updated. All operands arrive shard-local ([1, ...] rows of the
        [dp, ...] host arrays): tokens [1, C], slot [1] (LOCAL slot
        index, clipped-valid when idle), start [1] (the chunk's first
        write row — the contiguous window slide / paged absolute start,
        exactly ``prefill_chunked``'s convention), valid [1] (real token
        count; 0 = idle lane). ``rest`` carries (key [1, 2], temperature
        [1], top_k [1], top_p [1]) on a sample_on_device engine and the
        lane's adapter id [1] on a tenancy engine.

        The body IS the serial chunk program's: same B = 1 slot view,
        same batched-scatter cache_write, same ``pos_q = start + s``
        rows, same last-valid-token head slice, same epilogue from the
        same key — so every K/V byte and every logit bit matches what a
        separate ``prefill_chunked`` dispatch would have produced. An
        idle lane still traces (shape stability = one compile): its
        writes are where'd out (contiguous) or land on this shard's NULL
        scratch page (paged), its lengths stay untouched, and its
        sampled token is garbage the host discards. Unlike the serial
        chunk program there is NO dp owner psum — each shard runs its
        OWN lane and keeps its result in its [dp] output row. Returns
        (cache, the sampled token or logits row, the lane's hidden state
        or None)."""
        cfg = self.cfg
        rest = list(rest)
        sample = ()
        if self.sample_on_device:
            key, s_temp, s_topk, s_topp = rest[:4]
            rest = rest[4:]
            sample = (key[0], s_temp, s_topk, s_topp)
        C = tokens.shape[1]
        slot_i = jnp.asarray(slot[0], jnp.int32)
        start_i = jnp.asarray(start[0], jnp.int32)
        valid_i = jnp.asarray(valid[0], jnp.int32)
        active = valid_i > 0
        lane_params = params
        if self.adapters is not None:
            # the decode binding carried per-slot ids [L, local slots];
            # the lane's B = 1 compute needs ITS row — rebind in-trace
            # (same {"w","a","b","ids"} leaf form the serial chunk
            # dispatch binds host-side)
            adapter = rest[0]
            L = cfg.model.num_hidden_layers
            ids1 = jnp.broadcast_to(
                jnp.asarray(adapter, jnp.int32)[None, :], (L, 1))
            layers = dict(params["layers"])
            for name in llama.QUANT_WEIGHT_LEAVES:
                layers[name] = {**layers[name], "ids": ids1}
            lane_params = {**params, "layers": layers}
        pos_rows = (start_i + jnp.arange(C, dtype=jnp.int32))[None, :]
        cos_b, sin_b = rope_at_positions(self._cos, self._sin, pos_rows)
        h = llama.embed_lookup(lane_params["embed"],
                               tokens).astype(self._dt)
        lengths = cache["lengths"]
        pos = jnp.full((1,), start_i, jnp.int32)
        h, new_leaves = self._scan_layers(
            lane_params, cache, h, cos_b, sin_b, pos,
            self._slot_meta(cache, slot_i, active))
        idx = jnp.clip(valid_i - 1, 0, C - 1)
        h_last = jnp.take_along_axis(
            h, jnp.full((1, 1, 1), idx, jnp.int32), axis=1)
        last = tp_gather(llama.head_logits(lane_params, h_last, cfg))[:, 0]
        last = last.astype(jnp.float32)
        new_lengths = jnp.where(active,
                                lengths.at[slot_i].set(start_i + valid_i),
                                lengths)
        new_cache = self._rebuild(cache, new_leaves, new_lengths)
        out = self._epilogue(last, *sample) if self.sample_on_device \
            else last
        return (new_cache, out,
                h_last[:, 0] if self.return_hidden else None)

    # ---- host-facing API ---------------------------------------------------

    def _strip_stats(self, out: tuple) -> tuple:
        """Take a counting block's stats (the dispatch's last output,
        [layers, counters] int32) off ``out`` and keep it for
        ``take_stats``; every caller sees the tuple the Llama block
        returns."""
        if not self._n_stats:
            return out
        self._keep_stats(out[-1])
        return out[:-1]

    def _keep_stats(self, stats) -> None:
        """Keep a dispatch's stats for ``take_stats``. The copy to the host
        is asked for here, so it leaves with the program's end and the read
        at delivery (``step/deliver``) waits for nothing."""
        stats.copy_to_host_async()
        self._stats_pending.append(stats)

    def take_stats(self):
        """What was counted in the dispatches since the last call, summed
        over them and over the layers (int64, one number for each of
        ``stat_names``; None where nothing counts). A dispatch's stats are
        a row a layer of the block's own counters, or of a round of blocks
        one vector already summed, the round's counters behind the block's.
        The batcher calls it where it delivers a round, when the round's
        results are on the host anyway."""
        if not self.stat_names:
            return None
        pending, self._stats_pending = self._stats_pending, []
        total = np.zeros(len(self.stat_names), np.int64)
        for v in pending:
            v = np.asarray(v, np.int64)
            v = v.sum(axis=0) if v.ndim == 2 else v
            total[:len(v)] += v
        return total

    def shard_params(self, params):
        """Place a (global) parameter pytree onto this engine's mesh with
        the model's training shardings — TP column/row splits land on their
        devices, no resharding at step time."""
        return jax.tree.map(jax.device_put, params,
                            named_shardings(self.topo, self._pspecs))

    # ---- multi-tenant adapters (inference/tenancy.py) ----------------------

    def _adapter_leaves(self) -> dict:
        """The pack's device arrays, placed with the engine's adapter
        shardings (cached inside the pack by version, so hot add/remove
        re-places at the next dispatch and steady state pays nothing)."""
        return self.adapters.device_leaves(
            lambda name, side, arr: jax.device_put(
                arr, self._adapter_sh[name][side]))

    def bind_adapter_ids(self, params, adapter_ids, n: int):
        """Wrap ``params`` with the adapter pack + this dispatch's
        per-row adapter slot ids (``adapter_ids`` — [n] ints, or None
        for all-null). The segmented matmul gathers each row's pair, so
        one dispatch mixes tenants; slot 0 rows bypass exactly. On an
        engine without an adapter pack this is the identity (and passing
        ids is an error — the caller thinks tenants exist)."""
        if self.adapters is None:
            if adapter_ids is not None:
                raise ValueError(
                    "engine has no adapter pack (construct with "
                    "adapters=tenancy.AdapterPack) but adapter ids were "
                    "passed")
            return params
        if adapter_ids is None:
            ids = np.zeros(n, np.int32)
        else:
            ids = np.asarray(adapter_ids, np.int32).reshape(-1)
            if ids.shape[0] != n:
                raise ValueError(
                    f"adapter_ids has {ids.shape[0]} rows; this dispatch "
                    f"carries {n}")
            if (ids < 0).any() or (ids >= self.adapters.slots).any():
                raise ValueError(
                    f"adapter slot ids must be in [0, "
                    f"{self.adapters.slots}); got {ids.tolist()}")
        return llama.bind_adapters(params, self._adapter_leaves(),
                                   jnp.asarray(ids))

    def init_cache(self) -> dict:
        """Fresh zeroed cache, sharded on the engine mesh. For the paged
        layout this also resets the host allocator (pool, radix cache,
        block tables) — a new cache means every parked byte is gone, so
        the batcher's cache-lost rebuild gets a coherent empty pool."""
        if self.paged is not None:
            self.paged.reset()
        cache = self._init_cache_jit()
        if self.store is not None:
            # a new cache forgets what was retained, as the paged pool does
            self.store.reset()
            del self._store_pending[:]
            if self._store_pool is None:
                # the pool, and its two copy programs compiled with it on
                # pages nobody reads: the first hit finds them built
                self._store_pool = self._init_store_jit()
                null = np.zeros(self.store.max_pages, np.int32)
                self._store_pool = self._retain_jit(self._store_pool, cache,
                                                    0, null)
                cache = self._seat_jit(cache, self._store_pool, 0, null, 0)
        return cache

    def make_draft_program(self, with_head: bool = False):
        """Build the learned drafter's jitted dispatch (EAGLE-style —
        Li et al. 2024: draft from the target's own last hidden state,
        reusing its embedding and lm_head; Medusa-style cheap heads are
        the degenerate no-trunk case). One small program proposes
        ``spec_len`` greedy continuation tokens for EVERY slot:

            (params[, head], hidden [B, H], tokens [B]) -> drafts [B, G]

        Each step folds the current token's embedding into the running
        pseudo-hidden state (``hidden + embed(tok)`` — the residual-merge
        default that needs NO extra parameters, or ``tanh(concat(embed,
        hidden) @ head['w'])`` when tiny-head params are supplied, e.g.
        via ``checkpoint.load_params``), reads the shared LM head over it
        (final norm included — the exact logits path the target uses) and
        takes the argmax. Deterministic by construction, so the proposal
        is the point-mass distribution ``sampling.speculative_accept``
        assumes. No KV is read or written: the whole draft costs
        ``spec_len`` embedding rows + head matmuls — the "small jitted
        dispatch" next to a verify's full model pass."""
        if self.spec_len < 1:
            raise ValueError(
                "make_draft_program needs a speculative engine "
                "(spec_len > 0)")
        G = self.spec_len
        cfg = self.cfg

        def impl(params, *args):
            if with_head:
                head, hidden, tok = args
            else:
                hidden, tok = args
                head = None

            def step(carry, _):
                h, t = carry
                e = llama.embed_lookup(
                    params["embed"], t[:, None])[:, 0].astype(h.dtype)
                if head is not None:
                    x = jnp.tanh(jnp.concatenate([e, h], axis=-1)
                                 @ head["w"].astype(h.dtype))
                else:
                    x = h + e
                logits = tp_gather(
                    llama.head_logits(params, x[:, None, :], cfg))[:, 0]
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (x, nxt), nxt

            (_, _), out = lax.scan(step, (hidden, tok), None, length=G)
            return jnp.swapaxes(out, 0, 1)  # [B, G]

        head_spec = ({"w": P()},) if with_head else ()
        # base pspecs, NOT the adapter-wrapped dispatch specs: the draft
        # reads only embed/final_norm/lm_head, and its caller (the
        # LearnedDrafter) holds the UNBOUND base tree — adapters shape
        # per-token logits through verify, never through the draft.
        # Per-slot rows shard over dp like every batch family (the draft
        # is embarrassingly parallel over slots — no cache, no cross-row
        # reads).
        dpP = P("dp") if self.dp_size > 1 else P()
        return jax.jit(shard_map(
            impl, self.topo.mesh,
            in_specs=(self._pspecs,) + head_spec + (dpP, dpP),
            out_specs=dpP))

    # ---- paged-layout host plumbing ---------------------------------------

    def _sync_tables(self, cache) -> dict:
        """Ship the host allocator's block-table master to the device
        (replacing the donated copy the last dispatch consumed). Tiny
        ([slots, max_pages] int32) and unconditional — simpler than dirty
        tracking and invisible next to a model dispatch. hot_bf16 policy
        engines refresh the per-page read flags from live refcounts in
        the same breath, so sharing changes take effect next dispatch.

        The master is COPIED before it is handed over: the CPU backend
        takes an aligned numpy array without copying it, dispatches run
        asynchronously, and the allocator mutates its master in place —
        an aliased table let a still-queued insert see the NEXT dispatch's
        copy-on-write and park a prompt in the wrong page (the one-run-in-
        ten failure of test_ragged_verify_matches_per_slot_sequential)."""
        out = {**cache,
               "block_tables": jnp.asarray(self.paged.tables.copy())}
        if self.page_policy:
            out["page_quant"] = jnp.asarray(self.paged.quant_flags())
        return out

    def _ensure(self, cache, slot: int, from_pos: int, to_pos: int) -> dict:
        """Make rows [from_pos, to_pos) of ``slot`` writable before a
        dispatch: the allocator allocates growth pages and plans
        copy-on-writes; the (src, dst) pairs run here as byte-exact
        device page copies. After this, no write the dispatch performs
        can touch a page anyone else holds."""
        for src, dst in self.paged.ensure_writable(slot, from_pos, to_pos):
            cache = self._copy_page_jit(cache, src, dst)
        return cache

    def _pre_write(self, cache, nwrite: int, budget=None,
                   lead=None) -> dict:
        """Before a decode/verify dispatch: every PARKED slot (length > 0)
        writes up to ``nwrite`` rows from its current length — including
        inactive slots' recomputed ghost rows, which the mask hides but
        which must still never land in a shared page. Ensure + COW them
        all, then sync the tables. ``budget`` (decode blocks) caps each
        slot's reach at ``budget[s] + 1`` rows — the emitted run plus the
        one ghost row a stopped slot keeps rewriting — so page demand
        tracks what the dispatch can actually produce, which is what the
        batcher's admission pricing reserves.

        ``lead`` [slots] (overlap pipeline, defer_advance) is the extra
        reach the IN-FLIGHT round may still add to each slot:
        ``host_len`` is one round stale at issue time, so the true device
        length sits anywhere in [host_len, host_len + lead[s]] — the
        ensure window stretches by lead[s] to cover every row the stacked
        rounds can touch. Re-ensuring rows the previous round already
        owns is a no-op (exclusive pages stay exclusive), so the stretch
        costs nothing in steady state."""
        p = self.paged
        window = p.max_pages * p.page_len
        if budget is not None:
            budget = np.asarray(budget)
        if lead is not None:
            lead = np.asarray(lead)
        for s in np.flatnonzero(p.host_len > 0):
            n = nwrite if budget is None else min(
                nwrite, int(budget[s]) + 1)
            if lead is not None:
                n += int(lead[s])
            cache = self._ensure(cache, int(s), int(p.host_len[s]),
                                 min(int(p.host_len[s]) + n, window))
        return self._sync_tables(cache)

    def apply_advance(self, counts) -> None:
        """Deferred paged length advance (overlap pipeline): when
        ``defer_advance`` is set, decode_block/verify skip their host_len
        bookkeeping at issue time — the per-slot counts are still futures
        — and the batcher's sync stage calls this with the materialized
        (and late-finish-masked) counts instead. No-op on contiguous
        engines, whose device-side length pointers are the only length
        state."""
        if self.paged is not None:
            self.paged.advance(np.asarray(counts, np.int64))

    def prefill_bucket(self, prompt_len: int) -> int:
        """Power-of-two padding bucket for a prompt (one compile each)."""
        if prompt_len > self.max_seq_len:
            raise ValueError(
                f"prompt of {prompt_len} tokens exceeds max_seq_len "
                f"{self.max_seq_len}")
        b = self.min_prefill_bucket
        while b < prompt_len:
            b *= 2
        return min(b, self.max_seq_len)

    def _sample_args(self, sample) -> tuple:
        """Normalize a host caller's ``sample=(key, temperature, top_k,
        top_p)`` into the epilogue's device operands — and enforce that
        callers and the engine agree on WHERE sampling happens, so a
        host-sampling caller can never silently read a token id as
        logits (or vice versa)."""
        if not self.sample_on_device:
            if sample is not None:
                raise ValueError(
                    "this engine samples host-side (inference."
                    "sample_on_device: false); drop the sample argument "
                    "or build the engine with sample_on_device=True")
            return ()
        if sample is None:
            raise ValueError(
                "this engine runs the on-device sampling epilogue "
                "(inference.sample_on_device: true); pass sample=(key, "
                "temperature, top_k, top_p) so the dispatch can draw the "
                "next token without shipping logits to the host")
        key, temperature, top_k, top_p = sample
        return (jnp.asarray(key),
                jnp.asarray(np.asarray(temperature, np.float32).reshape(1)),
                jnp.asarray(np.asarray(top_k, np.int32).reshape(1)),
                jnp.asarray(np.asarray(top_p, np.float32).reshape(1)))

    def _lane_args(self, lanes) -> tuple:
        """Build the mixed programs' lane operand tail from per-shard
        lane feeds. ``lanes`` is None (every lane idle) or a list of
        ``dp_size`` entries, each None or a dict with ``slot`` (GLOBAL
        slot id on that shard), ``tokens`` (the chunk's 1..prefill_chunk
        real token ids), ``start`` (first write row — the caller applies
        the contiguous window slide / paged absolute convention,
        ``prefill_chunked``'s exact rule), and on a sample_on_device
        engine ``key``/``temperature``/``top_k``/``top_p`` (the SAME
        fold-at-len(prompt)-1 key every chunk of the serial path
        samples with), plus ``adapter`` on a tenancy engine. Idle lanes
        pad to fixed shapes (valid = 0) so the compiled program never
        changes."""
        dp = self.dp_size
        C = self.prefill_chunk
        toks = np.zeros((dp, C), np.int32)
        slot = np.zeros(dp, np.int32)
        start = np.zeros(dp, np.int32)
        valid = np.zeros(dp, np.int32)
        keyrows = np.zeros((dp, 2), np.uint32)
        temp = np.ones(dp, np.float32)
        topk = np.zeros(dp, np.int32)
        topp = np.ones(dp, np.float32)
        adapter = np.zeros(dp, np.int32)
        if lanes is not None:
            if len(lanes) != dp:
                raise ValueError(
                    f"lanes carries {len(lanes)} entries; this engine "
                    f"serves one lane per dp shard ({dp})")
            for sh, ln in enumerate(lanes):
                if ln is None:
                    continue
                g = int(ln["slot"])
                lo = sh * self.slots_per_shard
                if not lo <= g < lo + self.slots_per_shard:
                    raise ValueError(
                        f"lane slot {g} does not live on dp shard {sh} "
                        f"(slots [{lo}, {lo + self.slots_per_shard}))")
                chunk = np.asarray(ln["tokens"], np.int32).reshape(-1)
                if not 0 < chunk.size <= C:
                    raise ValueError(
                        f"lane chunk must carry 1..prefill_chunk ({C}) "
                        f"real tokens; got {chunk.size}")
                slot[sh] = g - lo
                start[sh] = int(ln["start"])
                toks[sh, : chunk.size] = chunk
                valid[sh] = chunk.size
                self.prefill_rows_total.inc(C)
                if self.sample_on_device:
                    keyrows[sh] = np.asarray(ln["key"]).reshape(2)
                    temp[sh] = np.float32(ln.get("temperature", 1.0))
                    topk[sh] = np.int32(ln.get("top_k", 0))
                    topp[sh] = np.float32(ln.get("top_p", 1.0))
                if self.adapters is not None:
                    adapter[sh] = int(ln.get("adapter") or 0)
        args = (jnp.asarray(toks), jnp.asarray(slot), jnp.asarray(start),
                jnp.asarray(valid))
        if self.sample_on_device:
            args += (jnp.asarray(keyrows), jnp.asarray(temp),
                     jnp.asarray(topk), jnp.asarray(topp))
        if self.adapters is not None:
            args += (jnp.asarray(adapter),)
        return args

    def _lane_ensure(self, cache, lanes) -> dict:
        """Paged pre-write for the lane chunks: make every active lane's
        real rows [start, start + len(tokens)) writable (growth alloc +
        COW) BEFORE the fused dispatch — the caller's ``_pre_write``
        follows and ships the synced tables. Trailing pad rows target
        unallocated table entries and drop to the NULL page, exactly
        like the serial chunk dispatch."""
        if self.paged is None or lanes is None:
            return cache
        for ln in lanes:
            if ln is None:
                continue
            s0 = int(ln["start"])
            n = int(np.asarray(ln["tokens"]).reshape(-1).size)
            cache = self._ensure(cache, int(ln["slot"]), s0, s0 + n)
        return cache

    def prefill(self, params, prompt_ids, sample=None,
                adapter_id=None) -> tuple:
        """Run one prompt through the full-sequence model. Returns
        (kv_blocks, last_logits [1, V] fp32) — or, on a
        ``sample_on_device`` engine (which REQUIRES ``sample=(key,
        temperature, top_k, top_p)``), (kv_blocks, sampled token [1]
        int32): the fused epilogue draws the first generated token inside
        the dispatch and the full-vocab logits never cross to the host.
        A ``return_hidden`` engine appends the prompt's last-token
        pre-final-norm hidden state [1, H]. Pads to the prompt's bucket
        host-side; jit reuses one executable per bucket size."""
        samp = self._sample_args(sample)
        if self.adapters is not None or adapter_id is not None:
            params = self.bind_adapter_ids(
                params, None if adapter_id is None else [adapter_id], 1)
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if ids.size == 0:
            raise ValueError("empty prompt")
        bucket = self.prefill_bucket(ids.size)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : ids.size] = ids
        self._hook("prefill")
        self.prefill_rows_total.inc(bucket)
        # resolved inside the lambda like every hot-path program, so the
        # flash->dense fallback's rebuilt jit is what a re-dispatch runs
        return self._strip_stats(self._dispatch(lambda: self._prefill_jit(
            params, jnp.asarray(padded),
            jnp.asarray([ids.size], jnp.int32), *samp)))

    def prefill_chunked(self, params, cache, prompt_ids, slot: int,
                        start: int = 0, sample=None,
                        adapter_id=None) -> tuple:
        """Prefill one prompt as fixed-width chunk dispatches writing K/V
        straight into ``slot`` (consumes ``cache``). Returns (cache,
        last_logits [1, V] fp32) — or (cache, sampled token [1] int32) on
        a ``sample_on_device`` engine: every chunk runs the epilogue from
        the SAME key (only the final chunk's draw is consumed, so the key
        chain matches the host sampler's exactly) and no chunk ever ships
        logits. One compiled shape regardless of prompt length; the
        ragged final chunk pads to the chunk width with rows past the
        final length unreachable. Behind the prefix store a chunk of at
        most ``NARROW_CHUNK`` real rows pads to that width instead
        (``chunk_width``): the same body at a second operand shape, which
        computes the rows the wide one computes for those tokens.

        ``start`` > 0 resumes past an already-parked prefix (the paged
        prefix-sharing admission: rows [0, start) are cached pages the
        chunks attend over but never recompute). ``prompt_ids`` is always
        the FULL prompt — chunk positions are absolute."""
        samp = self._sample_args(sample)
        if self.adapters is not None or adapter_id is not None:
            params = self.bind_adapter_ids(
                params, None if adapter_id is None else [adapter_id], 1)
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if ids.size == 0:
            raise ValueError("empty prompt")
        if ids.size > self.max_seq_len:
            raise ValueError(
                f"prompt of {ids.size} tokens exceeds max_seq_len "
                f"{self.max_seq_len}")
        if not 0 <= start < ids.size:
            raise ValueError(
                f"chunked-prefill start {start} outside prompt of "
                f"{ids.size} tokens")
        logits = None
        hidden = None
        for s0 in range(start, ids.size, self.prefill_chunk):
            end = min(s0 + self.prefill_chunk, ids.size)
            C = self.chunk_width(end - s0)  # the width dispatched
            if self.paged is None:
                # the write window is the chunk's full [w0, w0 + C) rows;
                # past max_seq_len, dynamic_update_slice would CLAMP the
                # start and silently shift the chunk onto earlier rows —
                # instead slide the window back and re-feed the overlap
                # tokens, whose rows recompute to the values already
                # parked there (same prefix, same positions, same program)
                w0 = min(s0, self.max_seq_len - C)
            else:
                # the paged scatter has no clamp hazard (rows past the
                # window drop to the NULL page), so the chunk never
                # slides — critical for the prefix-sharing resume, where
                # a slid window would re-feed (and pointlessly COW) the
                # shared prefix it exists to skip
                w0 = s0
            chunk = ids[w0:end]
            padded = np.zeros((1, C), np.int32)
            padded[0, : chunk.size] = chunk
            if self.paged is not None:
                # COW/alloc every page holding REAL chunk rows ([w0, end)
                # — the trailing pad rows target unallocated entries and
                # drop to the NULL page)
                cache = self._ensure(cache, slot, w0, end)
                cache = self._sync_tables(cache)
            self._hook("prefill_chunk")
            self.prefill_rows_total.inc(C)
            self.prefill_chunks_total[C].inc()
            out = self._chunk(params, cache, padded, slot, w0, chunk.size,
                              samp)
            if self.return_hidden:
                cache, logits, hidden = out
            else:
                cache, logits = out
            if self.paged is not None:
                self.paged.set_len(slot, end)
        if self.return_hidden:
            # the FINAL chunk's last-token hidden state is the prompt's
            return cache, logits, hidden
        return cache, logits

    def _chunk(self, params, cache, padded, slot: int, w0: int, valid: int,
               samp: tuple) -> tuple:
        """One dispatch of the chunk program, ``padded`` [1, width] wide."""
        return self._strip_stats(self._dispatch(
            lambda: self._prefill_chunk_jit(
                params, cache, jnp.asarray(padded),
                jnp.asarray(slot, jnp.int32), jnp.asarray(w0, jnp.int32),
                jnp.asarray(valid, jnp.int32), *samp)))

    def chunk_width(self, rows: int) -> int:
        """Rows the chunk program is dispatched with for a chunk that
        holds ``rows`` real ones: ``prefill_chunk``, or ``NARROW_CHUNK``
        for at most that many in an engine that holds the prefix store
        (``narrow_chunk``: a resumed suffix is a question of a few dozen
        tokens, and ``build_narrow`` has the second shape compiled). Read
        off the lengths; no option. Every other engine runs one width, as
        its rings, scans and carried states are sized to."""
        return (self.narrow_chunk if rows <= self.narrow_chunk
                else self.prefill_chunk)

    def prefill_widths(self, prompt_len: int, cached: int = 0) -> list:
        """Rows, padding included, of each prefill program a prompt runs
        past ``cached`` parked tokens: the one one-shot bucket of a prompt
        at or under a chunk with nothing parked, else its chunks."""
        if not cached and prompt_len <= self.prefill_chunk:
            return [self.prefill_bucket(prompt_len)]
        full, tail = divmod(prompt_len - cached, self.prefill_chunk)
        return ([self.prefill_chunk] * full
                + ([self.chunk_width(tail)] if tail else []))

    def build_narrow(self, params, cache) -> dict:
        """The narrow chunk program compiled and run before anything waits
        on it (consumes ``cache``): one dispatch of one valid row into slot
        0 of a fresh cache, the slot's length set back to 0 behind it, as
        ``init_cache`` builds the store's two copy programs on pages nobody
        reads. A warm-up of fresh prompts never resumes a short suffix, so
        without this the first hit would compile inside somebody's
        admission. Once a process, by the first batcher: a rebuilt cache
        finds the program built. Nothing where no chunk runs narrow."""
        if not self.narrow_chunk or self._narrow_built:
            return cache
        samp = (self._sample_args((jax.random.PRNGKey(0), 0.0, 0, 1.0))
                if self.sample_on_device else ())
        out = self._chunk(params, cache,
                          np.zeros((1, self.narrow_chunk), np.int32), 0, 0, 1,
                          samp)
        self._narrow_built = True
        return self.release(out[0], 0)

    def prefill_paged(self, params, cache, prompt_ids, slot: int,
                      sample=None, adapter_id=None,
                      cache_salt: str = "") -> tuple:
        """Paged admission: prefix-match, share, and prefill one prompt
        into ``slot`` (consumes ``cache``). Returns (cache, last_logits
        [1, V] fp32 — or the sampled token [1] int32 on a
        ``sample_on_device`` engine — n_dispatches, cached_tokens).

        The radix cache resolves the longest cached prefix; its pages are
        shared into the slot (refcount bumps — ZERO prefill work for
        those tokens) and only the suffix runs through the model, as
        chunk dispatches attending over the shared pages. A miss takes
        exactly the contiguous path's dispatches (pow-2-bucketed one-shot
        at or under ``prefill_chunk``, chunked above it) so paged-vs-
        contiguous generations stay bit-identical. Either way the
        prompt's pages are then registered in the radix cache for the
        next request — the first decode write past the prompt COWs the
        tail page rather than mutate what the cache now holds."""
        if self.paged is None:
            raise ValueError("prefill_paged needs kv_layout='paged'")
        ids = [int(t) for t in np.asarray(prompt_ids, np.int32).reshape(-1)]
        if not ids:
            raise ValueError("empty prompt")
        rh = self.return_hidden
        hidden = None
        cached = self.paged.match_prefix(slot, ids, salt=cache_salt)
        if cached > 0:
            cache = self._set_length_jit(self._sync_tables(cache), slot,
                                         cached)
            out = self.prefill_chunked(params, cache, ids, slot,
                                       start=cached, sample=sample,
                                       adapter_id=adapter_id)
            cache, logits = out[:2]
            hidden = out[2] if rh else None
            n = -(-(len(ids) - cached) // self.prefill_chunk)
        elif len(ids) <= self.prefill_chunk:
            out = self.prefill(params, ids, sample=sample,
                               adapter_id=adapter_id)
            kv, logits = out[:2]
            hidden = out[2] if rh else None
            cache = self.insert(cache, kv, slot, len(ids))
            n = 1
        else:
            out = self.prefill_chunked(params, cache, ids, slot,
                                       sample=sample, adapter_id=adapter_id)
            cache, logits = out[:2]
            hidden = out[2] if rh else None
            n = -(-len(ids) // self.prefill_chunk)
        self.paged.register_prompt(slot, ids, salt=cache_salt)
        base = (cache, logits, n, cached)
        return base + (hidden,) if rh else base

    def prefill_stored(self, params, cache, prompt_ids, slot: int,
                       sample=None, adapter_id=None,
                       cache_salt: str = "") -> tuple:
        """Contiguous admission through the prefix store (consumes
        ``cache``): what ``prefill_paged`` returns, (cache, last_logits
        [1, V] fp32 — or the sampled token on a ``sample_on_device`` engine
        — n_dispatches, cached_tokens[, hidden]).

        The store resolves the longest retained prefix of the prompt in
        whole pages (``PrefixStore.lookup``, within ``cache_salt``'s
        domain). A hit COPIES those pages into rows ``[0, cached)`` of the
        slot's strip (``_store_seat``: one program, the slot's length set
        with it) and the rest runs through the chunk program,
        ``prefill_chunked(start=cached)``, whatever its length: whole
        ``prefill_chunk``-row chunks and then, for a remainder of at most
        ``NARROW_CHUNK`` rows, one chunk of that width (``chunk_width``),
        both shapes compiled in set-up (``build_narrow``). The hit is taken
        where it leaves fewer rows for the programs to run, padding
        included (``prefill_widths``): a prompt the one-shot program takes
        in a bucket no wider than the suffix's chunks is prefilled as
        before (``smollm-1.7b.serve-batch`` asks its eight prompts over and
        over, some of them in a 64- or 128-row bucket: PERF.md section 6,
        PR 49 and PR 61). A miss takes exactly the dispatches the
        store-less engine takes, but for a last chunk's width.

        Either way the prompt's own whole pages the trie does not hold yet
        are owed to the store (``_store_pending``) and copied strip -> pool,
        for the next prompt that begins with them, once the next round is
        on the device (``_store_flush``, from ``_round``): the bookkeeping
        and the copy's enqueue then run while the host would wait for the
        round, and the copy itself in the gap the device has between two
        rounds, where inside admission every running stream would wait for
        both."""
        ids = [int(t) for t in np.asarray(prompt_ids, np.int32).reshape(-1)]
        if not ids:
            raise ValueError("empty prompt")
        # a prompt parked in this slot and still owed (no round ran since)
        # is copied now, from rows this one is about to overwrite
        self._store_flush(cache, slot)
        cache, cached = self._store_seat(cache, ids, slot, cache_salt)
        n = len(self.prefill_widths(len(ids), cached))
        if cached > 0 or len(ids) > self.prefill_chunk:
            out = self.prefill_chunked(params, cache, ids, slot,
                                       start=cached, sample=sample,
                                       adapter_id=adapter_id)
            cache = out[0]
        else:
            out = self.prefill(params, ids, sample=sample,
                               adapter_id=adapter_id)
            cache = self.insert(cache, out[0], slot, len(ids))
        self._store_pending.append((ids, slot, cache_salt))
        return (cache, out[1], n, cached) + tuple(out[2:])

    def _store_flush(self, cache, slot: Optional[int] = None) -> None:
        """Retain what is owed (of ``slot`` alone, if given) from ``cache``,
        which is only read: the rows of a parked prompt stay as its prefill
        wrote them whatever its slot decodes behind them."""
        owed = [e for e in self._store_pending
                if slot is None or e[1] == slot]
        for entry in owed:
            self._store_pending.remove(entry)
            self._store_retain(cache, *entry)

    def _store_seat(self, cache, ids, slot: int, salt: str = "") -> tuple:
        """The hit: (cache, cached) with the longest retained prefix of
        ``ids``, ``cached`` tokens in whole pages, copied into ``slot``'s
        strip and its length set; the cache as it came and 0 on a miss,
        and where the copy would leave the prefill programs no fewer rows
        to run than the prompt prefilled whole."""
        whole = sum(self.prefill_widths(len(ids)))
        row, cached = self.store.lookup(
            ids, salt=salt, worth=lambda c: sum(
                self.prefill_widths(len(ids), c)) < whole)
        if cached:
            cache = self._seat_jit(cache, self._store_pool, slot, row,
                                   cached)
            self._store_counters["hits"].inc()
            self._store_counters["tokens_copied"].inc(cached)
        return cache, cached

    def _store_retain(self, cache, ids, slot: int, salt: str = "") -> None:
        """Retention: the whole pages of the prompt ``ids``, parked in
        ``slot``, that the trie does not hold yet are copied into pool
        pages (freed from the least recently used leaves where the pool is
        full) and the trie takes them. Keeps what fits, and never raises:
        a round or an admission does not fail for a page not kept."""
        evicted = self.store.radix.evictions
        plan = self.store.plan_retain(ids, salt=salt)
        self._store_counters["pages_evicted"].inc(
            self.store.radix.evictions - evicted)
        if plan is None:
            return
        row, chunk_pids = plan
        try:
            self._store_pool = self._retain_jit(self._store_pool, cache,
                                                slot, row)
        except Exception as e:  # noqa: BLE001 - the store starts over
            # the pool was donated to the dispatch that failed and may be
            # gone with it: forget what was retained and hold a fresh one
            log0(f"prefix store: retention failed ({type(e).__name__}: "
                 f"{e}); the store starts over empty", flush=True)
            self.store.reset()
            self._store_pool = self._init_store_jit()
            return
        self._store_counters["pages_retained"].inc(
            self.store.commit(ids, chunk_pids, salt=salt))

    # ---- page transport (prefill/decode disaggregation) -------------------

    def transport_spec(self) -> dict:
        """The engine's page-layout fingerprint for the KV-page transport
        (inference/page_transport.py) — what a peer must match to
        exchange page bytes with this replica."""
        from picotron_tpu.inference import page_transport

        return page_transport.transport_spec(self)

    def export_prefix(self, cache, ids, first_token=None,
                      cache_salt: str = "") -> dict:
        """Serialize the longest radix-cached prefix of ``ids`` as a
        transport payload (paged engines only): pinned pages, byte-exact
        leaves, CRC. ``first_token`` rides along when the match covers
        the whole prompt — the disaggregated handoff's seat state.
        ``cache_salt`` (the tenant) keys the lookup AND rides the
        payload, so a handoff can only land in the same tenant's
        subtree on the receiver."""
        from picotron_tpu.inference import page_transport

        return page_transport.export_prefix(self, cache, ids,
                                            first_token=first_token,
                                            tenant=cache_salt)

    def import_prefix(self, cache, payload) -> tuple:
        """Land a transport payload's pages in the local pool + radix
        cache (consumes ``cache``; returns (cache, info)). Only locally
        missing chunks allocate; failures release every allocated page
        before propagating (refcount-correct under the dispatch retry)."""
        from picotron_tpu.inference import page_transport

        return page_transport.import_prefix(self, cache, payload)

    def seat_slot(self, cache, slot: int, length: int) -> dict:
        """Park an imported, fully cached prefix as ``slot``'s
        ready-to-decode state (consumes ``cache``): device length pointer
        + synced tables, NO dispatch. The caller already shared the pages
        into the slot (``paged.match_prefix(..., cap_last=False)``)."""
        if self.paged is None:
            raise ValueError("seat_slot needs kv_layout='paged'")
        self.paged.set_len(slot, length)
        return self._set_length_jit(self._sync_tables(cache), slot, length)

    def _page_bytes(self) -> int:
        """Raw bytes one pool page holds across every storage leaf (the
        migration accounting unit — same figure the transport's
        ``bytes_total`` reports per page)."""
        spec = self.transport_spec()
        return sum(np.dtype(l["dtype"]).itemsize * int(np.prod(l["shape"]))
                   for l in spec["leaves"].values())

    def migrate_slot(self, cache, src: int, dst: int, prompt_ids=None,
                     cache_salt: str = "") -> tuple:
        """Move a parked slot's KV pages from global slot ``src`` into
        (empty) global slot ``dst`` through the page-transport device
        path — ONE batched gather + ONE donating write, byte-exact —
        then re-seat the slot's host/device state (consumes ``cache``).
        The dp rebalance planner's primitive: with ``dst`` on a
        different dp shard the pages land in THAT shard's pool strip, so
        a skewed shard sheds a whole parked slot. Works under dp == 1
        too (a plain slot move within one pool).

        All-or-nothing: destination-pool exhaustion
        (``PagePoolExhausted`` from the all-or-nothing allocation) or
        any fault before the donating write completes releases every
        destination page and leaves the source slot untouched —
        refcounts conserved either way. ``host_len`` already reflects
        only ACCEPTED tokens (a verify's advance ran before anyone could
        park the slot), so draft rows a speculative round wrote past the
        length pointer are rolled back by construction — never exported.

        ``prompt_ids`` (+ ``cache_salt`` = tenant) re-grafts the slot's
        prompt into the destination shard's radix domain, so prefix
        sharing survives the move. Returns (cache, bytes_moved)."""
        if self.paged is None:
            raise ValueError("migrate_slot needs kv_layout='paged'")
        p = self.paged
        if not (0 <= src < self.slots and 0 <= dst < self.slots):
            raise ValueError(
                f"migrate_slot: slots out of range: {src} -> {dst} "
                f"(engine has {self.slots})")
        if src == dst:
            return cache, 0
        n_tok = int(p.host_len[src])
        if n_tok <= 0:
            raise ValueError(f"migrate_slot: source slot {src} is empty")
        if int(p.host_len[dst]) > 0:
            raise ValueError(
                f"migrate_slot: destination slot {dst} is occupied")
        npages = p.pages_for(n_tok)
        src_pids = np.asarray(p.tables)[src, :npages].astype(np.int32)
        # all-or-nothing allocation on the DESTINATION slot's shard:
        # exhaustion raises here, before anything moved
        if self.dp_size > 1:
            dsh = p.shards[p.shard_of(dst)]
            base = p.shard_of(dst) * p.pages_per_shard
            new_pids = [base + q for q in dsh.alloc_import(npages)]
        else:
            dsh, base = p, 0
            new_pids = p.alloc_import(npages)
        bucket = 1
        while bucket < npages:
            bucket *= 2
        src_arr = np.full(bucket, paged_kv.NULL_PAGE, np.int32)
        src_arr[:npages] = src_pids
        dst_arr = np.full(bucket, paged_kv.NULL_PAGE, np.int32)
        dst_arr[:npages] = new_pids
        try:
            pages = self._gather_pages_jit(cache, src_arr)
            # a dead dp peer discovered here exits 77 BEFORE the donating
            # write; the except arm keeps restart leak-free regardless
            self._check_monitor()
            cache = self._write_pages_jit(cache, pages, dst_arr)
        except BaseException:
            # the fault struck before the donating dispatch consumed the
            # cache: the fresh pages' only holder is this migration —
            # release them and both pools are exactly as before
            p.release_pages(new_pids)
            raise
        # seat the destination: its table row holds the fresh pages
        # (refcount 1, already owed to the slot), master length/pricing
        # move over, then the source's references drop — shared source
        # pages live on under their other holders
        if self.dp_size > 1:
            dsh.tables[p.local_slot(dst), :npages] = \
                [q - base for q in new_pids]
        else:
            p.tables[dst, :npages] = new_pids
        p.priced[dst] = p.priced[src]
        p.set_len(dst, n_tok)
        p.free_slot(src)
        if prompt_ids is not None:
            p.register_prompt(dst, [int(t) for t in prompt_ids],
                              salt=cache_salt)
        cache = self._set_length_jit(self._sync_tables(cache), dst, n_tok)
        cache = self._release_jit(cache, src)
        return cache, npages * self._page_bytes()

    def insert(self, cache, kv, slot: int, length: int) -> dict:
        """Park a prefill's blocks into ``slot`` (consumes ``cache``).
        On the paged layout this first allocates the slot's pages host-
        side, then scatters the blocks through its block-table row."""
        if self.paged is not None:
            cache = self._ensure(cache, slot, 0, length)
            cache = self._sync_tables(cache)
            self.paged.set_len(slot, length)
        return self._insert_jit(cache, kv, slot, length)

    def release(self, cache, slot: int) -> dict:
        """Free a slot for the next request (consumes ``cache``). Paged:
        drop the slot's page references — exclusively-held pages return
        to the pool, pages shared with the radix cache (or other slots)
        live on for the next prefix hit."""
        if self.paged is not None:
            self.paged.free_slot(slot)
            cache = self._sync_tables(cache)
        return self._release_jit(cache, slot)

    def decode_step(self, params, cache, tokens, key, temperature,
                    top_k, top_p, adapter_ids=None) -> tuple:
        """One token for every slot. tokens/temperature/top_k/top_p are
        [slots] host or device arrays; returns (cache, next_tokens [slots],
        logits [slots, V] fp32). On a ``sample_on_device`` engine the
        logits slot is None — the [B, V] array never leaves the device
        (the [B] token ids are the dispatch's whole host payload). A
        ``return_hidden`` engine appends hidden [slots, H] (the step's
        pre-final-norm hidden states — the learned drafter's input).
        Consumes ``cache``."""
        if self.key_schedule == "slot":
            raise ValueError(
                "decode_step is round-keyed (one shared key per step) and "
                "a key_schedule='slot' engine samples with per-slot "
                "position-folded keys — use decode_block, which folds "
                "them in-trace")
        self._hook("decode")
        if self.adapters is not None or adapter_ids is not None:
            params = self.bind_adapter_ids(params, adapter_ids, self.slots)
        if self.paged is not None:
            cache = self._pre_write(cache, 1)
        out = self._strip_stats(self._dispatch(lambda: self._decode_jit(
            params, cache,
            jnp.asarray(np.asarray(tokens, np.int32)), key,
            jnp.asarray(np.asarray(temperature, np.float32)),
            jnp.asarray(np.asarray(top_k, np.int32)),
            jnp.asarray(np.asarray(top_p, np.float32)))))
        if self.paged is not None:
            # mirror the device rule: parked slots advanced by one
            self.paged.advance((self.paged.host_len > 0).astype(np.int64))
        if self.sample_on_device:
            if self.return_hidden:
                cache, toks, hid = out
                return cache, toks, None, hid
            cache, toks = out
            return cache, toks, None
        return out

    def round_keys(self, key) -> tuple:
        """One round of the batcher's key chain as ONE program and no
        host copy: ``key`` (placed with ``key_sharding``) -> (the carried
        key, keys [decode_block_len, 2]), both device arrays replicated
        over the mesh. ``keys[j]`` is what the j-th of ``decode_block_len``
        eager ``jax.random.split`` links would have handed out, bit for
        bit; ``decode_block`` takes the array as it is and donates
        nothing of it, so an isolation re-dispatch may reuse it."""
        return self._round_keys_jit(key, self.decode_block_len)

    def decode_block(self, params, cache, tokens, keys, eos_id, budget,
                     temperature, top_k, top_p, adapter_ids=None,
                     lead=None, lanes=None, given=None,
                     waiting=None) -> RoundResult:
        """``decode_block_len`` tokens for every slot in one dispatch.
        ``keys`` is [decode_block_len, 2] (one PRNG key per in-block step)
        on a round-keyed engine, or the per-slot BASE keys [slots, 2] on a
        ``key_schedule='slot'`` engine (positions fold in-trace);
        ``eos_id`` [slots] int32 (−1 = none), ``budget`` [slots] int32
        remaining tokens (0 for free slots). ``tokens`` may be a device
        array — it stays lazy (the overlap pipeline feeds the previous
        round's on-device next-token output straight back in). Returns a
        ``RoundResult``: tokens [slots, decode_block_len], produced counts
        [slots], and whatever else the engine's options add (``accepted``
        is None). Consumes ``cache``. ``lead`` forwards to ``_pre_write``
        (overlap's stale-host_len reach allowance); with ``defer_advance``
        set the paged length bookkeeping is skipped here — the caller's
        sync stage applies it (``apply_advance``).

        ``lanes`` (mixed_dispatch engines only — see ``_lane_args``)
        feeds each dp shard's fused prefill lane; a mixed engine ALWAYS
        runs the fused program (idle padded lanes when None), so the
        compiled shape never changes.

        An engine that generates by blocks (``self.blocks``) runs its round
        of blocks here (``_blocks_impl``): ``tokens`` is then [slots,
        block_length], the positions of each slot's next block that are
        given, and ``given`` [slots] says how many lead it (a prompt's
        remainder in the slot's first round, 0 after). ``waiting`` [slots,
        block_length] is each slot's waiting block: a round stores the K/V
        of every block but its last, whose tokens the caller hands back
        here with the slot's NEXT round (the given positions of that block,
        if any, and the tokens the round emitted behind them: a stream that
        goes on was handed the whole block), and that round's first forward
        stores it. A row of -1 (None: every row) says nothing waits: the
        slot's first round, or a caller that stored the block itself
        (``block_forward(commit=True)``). A stream that ended owes
        nothing."""
        if self.key_schedule != "slot":
            keys = jnp.asarray(keys)
            if keys.shape[0] != self.decode_block_len:
                raise ValueError(
                    f"keys has {keys.shape[0]} rows; decode_block_len is "
                    f"{self.decode_block_len} (one key per in-block step)")
        # a device tokens array must NOT round-trip through np.asarray —
        # that sync is exactly what the overlap pipeline exists to avoid
        if not isinstance(tokens, jax.Array):
            tokens = np.asarray(tokens, np.int32)
        if self.blocks:
            Bd = self.cfg.model.block_length
            given = np.asarray(given, np.int32)
            waiting = np.full((self.slots, Bd), -1, np.int32) \
                if waiting is None else np.asarray(waiting, np.int32)
            if tokens.shape != (self.slots, Bd) \
                    or waiting.shape != tokens.shape \
                    or given.shape != (self.slots,) \
                    or np.any(given < 0) or np.any(given >= Bd):
                raise ValueError(
                    f"a round of blocks takes tokens and waiting [slots, "
                    f"block_length] = [{self.slots}, {Bd}] and given "
                    f"[slots] in [0, block_length); got {tokens.shape}, "
                    f"{waiting.shape} and {given.tolist()}")
            tokens = np.concatenate([waiting, tokens], axis=1)
            return self._round("blocks", params, cache, (tokens, given),
                               keys, eos_id, budget, temperature, top_k,
                               top_p, self.decode_block_len, budget,
                               adapter_ids, lead, lanes)
        return self._round("decode_block", params, cache, (tokens,), keys,
                           eos_id, budget, temperature, top_k, top_p,
                           self.decode_block_len, budget, adapter_ids,
                           lead, lanes)

    def block_forward(self, params, cache, tokens, active,
                      commit: bool = False) -> tuple:
        """One forward of a round of blocks as a dispatch of its own, for
        the checks that hold every forward to the reference: ``tokens``
        [slots, block_length] is every slot's block as it stands (given and
        fixed positions, ``mask_token_id`` at the others), ``active``
        [slots] bool the slots it counts for. A denoise forward returns
        (cache, logits [slots, block_length, V] float32) and leaves
        ``lengths`` where they were; with ``commit`` (cache, None), the
        active slots' lengths past the block. Consumes ``cache``."""
        if not self.blocks:
            raise ValueError("block_forward is a blocks engine's")
        def prog():
            # looked up at the call: a flash->dense rebuild empties the table
            key = ("block_forward", commit)
            if key not in self._programs:
                self._programs[key] = jax.jit(
                    shard_map(
                        partial(self._block_forward_impl, commit=commit),
                        self.topo.mesh,
                        in_specs=(self._decode_dispatch_pspecs,
                                  self._cspecs, P(), P()),
                        out_specs=(self._cspecs, P(), P())),
                    donate_argnums=(1,))
            return self._programs[key]

        self._hook("block_forward")
        cache, logits, stats = self._dispatch(lambda: prog()(
            params, cache, jnp.asarray(np.asarray(tokens, np.int32)),
            jnp.asarray(np.asarray(active, bool))))
        self._keep_stats(stats)
        return cache, (None if commit else logits)

    def verify(self, params, cache, tokens, key, eos_id, budget,
               temperature, top_k, top_p, draft_len=None,
               adapter_ids=None, lead=None, lanes=None) -> RoundResult:
        """One speculative draft-verify dispatch for every slot
        (``spec_len > 0`` engines only). ``tokens`` is
        [slots, spec_len + 1] int32 — column 0 is each slot's current last
        token, columns 1..spec_len its drafted continuation; the remaining
        arguments are [slots] arrays exactly as ``decode_block`` takes
        them (on a ``key_schedule='slot'`` engine ``key`` is the per-slot
        base keys [slots, 2] and ``tokens`` may be a device array).
        ``draft_len`` [slots] int32 (optional) makes the dispatch
        RAGGED: slot b proposed only ``draft_len[b] <= spec_len`` real
        drafts (the controller's per-slot choice) — pad columns past it
        are masked out of acceptance and the K/V write while the compiled
        shape stays [slots, spec_len + 1], so mixed per-slot lengths cost
        no recompile. None = every slot drafted the full spec_len.
        Returns a ``RoundResult``: tokens (the emitted runs)
        [slots, spec_len + 1], counts [slots] — ``counts[b]`` leading
        entries of row b are the tokens slot b produced this dispatch
        (1..spec_len + 1 per active slot) — accepted-draft counts [slots],
        and whatever else the engine's options add. Consumes ``cache``.
        ``lead``/``defer_advance``/``lanes``: see ``decode_block``."""
        if self.spec_len < 1:
            raise ValueError(
                "speculative decoding is off for this engine (spec_len == "
                "0); construct it with spec_len > 0 or set "
                "inference.spec_len")
        # device tokens stay lazy (overlap feeds column 0 straight from
        # the previous round's on-device next-token output)
        if not isinstance(tokens, jax.Array):
            tokens = np.asarray(tokens, np.int32)
        if tuple(tokens.shape) != (self.slots, self.spec_len + 1):
            raise ValueError(
                f"verify tokens must be [slots, spec_len + 1] = "
                f"[{self.slots}, {self.spec_len + 1}]; got "
                f"{tuple(tokens.shape)}")
        if draft_len is None:
            valid = np.full(self.slots, self.spec_len + 1, np.int32)
        else:
            draft_len = np.asarray(draft_len, np.int32)
            if draft_len.shape != (self.slots,):
                raise ValueError(
                    f"draft_len must be [slots] = [{self.slots}]; got "
                    f"{draft_len.shape}")
            if np.any(draft_len < 0) or np.any(draft_len > self.spec_len):
                raise ValueError(
                    f"draft_len entries must be in [0, spec_len = "
                    f"{self.spec_len}]; got {draft_len.tolist()}")
            valid = draft_len + 1
        # the verify writes spec_len + 1 rows OPTIMISTICALLY for every
        # parked slot whatever its budget; ensuring them all exclusive
        # BEFORE the dispatch is what makes the rollback free — rejected
        # rows strand in pages only this slot holds, never in a shared one
        return self._round("verify", params, cache, (tokens, valid), key,
                           eos_id, budget, temperature, top_k, top_p,
                           self.spec_len + 1, None, adapter_ids, lead,
                           lanes)

    def _round_operands(self, rows, keys, eos_id, budget, temperature,
                        top_k, top_p, lanes) -> tuple:
        """A round program's operands after ``params`` and ``cache``
        (``issue/operands``): every [slots] row the host holds in ONE int32
        array [rows, slots] (``_unpack_rows`` is its reader: ``rows``,
        which is ``tokens`` and a verify's ``valid``, then ``_PACK_ROWS``,
        float32 rows as their bits), handed to the program's call as the
        host array it is, so the call makes the round's ONE copy up itself
        and no ``device_put`` of its own stands in front of it (0.3 ms a
        round on the chip, PERF.md section 6, PR 38); ``tokens`` as it is
        where it is a device array already (the overlap pipeline's); the
        keys, device arrays too; then the fused lane's operands."""
        tokens, *valid = rows
        dev = isinstance(tokens, jax.Array)
        bits = lambda row: np.asarray(row, np.float32).view(np.int32)
        # a verify's tokens [slots, S] ride as S rows, one a fed position
        host = [*(() if dev else tokens.reshape(self.slots, -1).T), *valid,
                eos_id, budget, top_k, bits(temperature), bits(top_p)]
        pack = np.array(host, np.int32)
        lane = self._lane_args(lanes) if self.mixed else ()
        self.count_copies("h2d", 1 + len(lane))
        return (pack, *((tokens,) if dev else ()), keys, *lane)

    def count_copies(self, direction: str, n: int = 1) -> None:
        """``n`` more copies between host and device (``"h2d"`` |
        ``"d2h"``) that a round made for its rows: the packed operand (and
        a mixed engine's lane operands) up, the packed outputs down. A
        serial round counts 1 and 1."""
        self.obs.registry.counter(
            "picotron_round_copies_total",
            "host-device copies of a round's rows and outputs, by "
            "direction", direction=direction).inc(n)

    def _round(self, kind: str, params, cache, rows, keys, eos_id, budget,
               temperature, top_k, top_p, nwrite: int, reach,
               adapter_ids, lead, lanes) -> RoundResult:
        """The host half ``decode_block`` and ``verify`` share (the
        batcher's ``step/issue``): checks, hooks, adapter binding, the
        paged pre-write of up to ``nwrite`` rows a slot (``reach``: see
        ``_pre_write``'s ``budget``), the operands made
        (``issue/operands``: the host rows packed; ``rows`` is ``tokens``,
        host or device, and a verify's ``valid``), the call of
        ``_program(kind)``, which takes the pack up with it, and the copy
        down of its packed outputs asked for at once (``issue/enqueue``),
        and the result by name. The two parts are timed where they happen
        (``obs.part``); the rest is ``step/issue``'s own time."""
        if lanes is not None and not self.mixed:
            raise ValueError(
                "lanes requires a mixed_dispatch engine (construct with "
                "mixed_dispatch=True or set inference.mixed_dispatch)")
        if self.key_schedule == "slot":
            # per-slot base keys [slots, 2]; positions fold in-trace
            keys = jnp.asarray(keys)
            if keys.shape != (self.slots, 2):
                raise ValueError(
                    f"key_schedule='slot' takes per-slot base keys "
                    f"[slots, 2] = [{self.slots}, 2]; got "
                    f"{tuple(keys.shape)}")
        hook = "decode" if kind == "decode_block" else kind
        self._hook(hook, budget)
        if self.adapters is not None or adapter_ids is not None:
            params = self.bind_adapter_ids(params, adapter_ids, self.slots)
        poison = self._poison(hook)
        if self.paged is not None:
            cache = self._lane_ensure(cache, lanes)
            cache = self._pre_write(cache, nwrite, budget=reach, lead=lead)
        with self.obs.part("issue/operands"):
            operands = self._round_operands(rows, keys, eos_id, budget,
                                            temperature, top_k, top_p, lanes)
        # the program is resolved INSIDE the lambda so the flash->dense
        # fallback's rebuilt table is what a re-dispatch reads
        dev = isinstance(rows[0], jax.Array)
        with self.obs.part("issue/enqueue"):
            out = dict(zip(self._round_fields(kind), self._dispatch(
                lambda: self._program(kind, poison, dev)(
                    params, cache, *operands))))
            # the copy down starts when the program ends, not when the
            # host has waited for it: the sync reads bytes that are there
            out["packed"].copy_to_host_async()
        if self._store_pending:
            # this round's admissions, retained behind it on the device
            self._store_flush(out["cache"])
        if "stats" in out:
            self._keep_stats(out.pop("stats"))  # for ``take_stats``
        lane = ((out.pop("lane_out"), out.pop("lane_hidden", None))
                if self.mixed else None)
        res = RoundResult(verify=kind == "verify", lane=lane, **out)
        if self.paged is not None and not self.defer_advance:
            # mirror device length advancement (counts per slot; a verify
            # advanced by the ACCEPTED counts — the length pointer is the
            # rollback). The host sync this forces is the round's ONE
            # sync, just moved ahead of the batcher's own read of the
            # same packed copy.
            self.paged.advance(res.counts.astype(np.int64))
        return res
