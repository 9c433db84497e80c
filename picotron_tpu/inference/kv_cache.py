"""Preallocated slot-based KV cache + the masked dot-product decode kernel.

The training stack has no notion of a past: ``models/llama.py`` recomputes
every key/value each step. Serving needs the opposite — each generated token
must attend over all previous keys without recomputing them — so the cache
preallocates the whole attention past once and every decode step writes one
row per sequence:

- ``k``/``v``: ``[num_layers, slots, max_seq_len, n_kv_heads / p,
  p * head_dim]`` — ``p`` neighbouring kv heads side by side in one row
  (``pack_factor``; "The packed row" below). ``p == 1`` for heads of 128
  and wider: the plain ``[.., n_kv_heads, head_dim]`` leaf.
  The layer axis leads (rather than the naive ``[batch, layers, ...]``
  ordering) so one layer is one contiguous ``[B, T, H, D]`` block — the
  layout ``ops/attention.py`` already uses — that a layer index addresses.
  The cache NEVER LEAVES ITS BUFFER inside a serving program: the stacked
  leaves ride the engine's layer scan as CARRY (the layer index is what
  the scan iterates over), ``cache_write`` scatters only the new rows at
  ``[layer, slot, pos]`` and ``attend`` reads the layer through that
  index, so a decode step moves one row per slot and reads the window
  once — no per-layer slice out, no write-back, no per-step copy of the
  cache. Heads are the COMPACT GQA count (``num_key_value_heads``,
  never repeated): repetition happens inside ``decode_attention`` via a
  grouped einsum, so GQA models pay ``Hkv/Hq`` of the naive cache bytes.
- ``lengths``: ``[slots]`` int32 — each sequence's write index (= tokens
  currently parked). Slot ``b``'s visible keys are ``t < lengths[b]``; a
  freed slot has ``lengths == 0`` and its stale rows are unreachable, which
  is what makes slot recycling (inference/batcher.py) a 1-element write.
- int8 mode (``inference.kv_cache_dtype: "int8"``): ``k``/``v`` store
  absmax-quantized int8 rows and the cache gains ``k_scale``/``v_scale``
  ``[num_layers, slots, max_seq_len, n_kv_heads]`` fp32 tensors — one scale
  per written row per kv head, so quantization error never crosses a head
  or a position. Quantization happens on write (``cache_write`` /
  prefill), dequantization inside ``attend`` right before the fp32-softmax
  attention. Cache bytes ≈ (1 + 4/head_dim) per element vs 2 for bf16 —
  ~53% at head_dim 64, i.e. ~2x the slots or context at the same HBM.

Sharding: the head axis shards over 'tp' — the same split as the wk/wv
columns that produce it — so a TP-sharded checkpoint decodes with zero
resharding; the scale tensors shard their (trailing) head axis the same
way; everything else is replicated (``cache_pspecs``). Unquantized dtype
follows the model's param dtype (bf16 on the production configs; fp32 tiny
CPU models stay exact against the ``forward_logits`` oracle).

The packed row. The TPU tiles an array's two minor dimensions into
(8, 128) registers, 128 lanes wide. A leaf whose minor dimension is a head
of 64 fills half of each lane row, so the compiler lays the resident
array out with the TOKENS minor-most (``{2,4,3,1,0}``, unpadded) and every
decode program converts it on entry to head-minor, lane-padded 64 -> 128
(twice the bytes), works on that, and converts it back on exit: at
SmolLM's 32 heads of 64 the compiled text held four whole-leaf copies
(``copy.18/.19`` in, ``copy.25/.26`` out; 17 % of the device's time in the
``smollm-1.7b.serve-batch`` trace of PR 30) and attention read a window
that was half padding. So a leaf's row is always whole lanes:
``p = 128 // head_dim`` neighbouring kv heads lie side by side in one
``p * head_dim``-wide row (``pack_factor``: when 128 divides by the head,
the head is narrower than 128 and the LOCAL kv head count after 'tp'
divides by ``p``; else ``p == 1``). The bytes are those of the row-major
``[.., n_kv_heads, head_dim]`` array, so packing fresh rows is a reshape
(``pack_heads``), the compiler keeps the leaf row-major (``{4,3,2,1,0}``)
and no program copies it (tests/test_chip_compile.py reads both off the
compiled text). ``decode_attention`` contracts whole rows: each query
head sits in its own head's lanes of a row that is zero elsewhere (an
exact zero times a finite key adds an exact zero), and of the value
contraction's ``p`` candidate blocks each head keeps its own lanes: twice
the step's attention FLOPs, K and V read from HBM once and unpadded. (What
the compiler makes of it, as of Mistral's heads of 128: a fusion slices
the layer of K, 33.5 MB at SmolLM's 4 x 2048, into on-chip memory at HBM
speed, and the contraction's fusion re-lays it head-major from there; the
two run one after the other, 42 + 37 us on a v5e, PERF.md PR 31, where one
pass at HBM speed would be 42: on a TPU the plain decode step therefore
runs ``ops/pallas/decode_attention.py::flash_decode_stacked`` over the
stacked leaf, rows whole, one pass, live rows only: ``attend``.) The pack
factor of a leaf is read off its own shape (``leaf.shape[-1] //
head_dim``); this module alone knows the layout, and whatever needs
``[.., n_kv_heads, head_dim]`` (the sliced flash decode kernel, the int8
scales) takes ``unpack_heads``. With ``p == 1`` every function here runs the
operations it ran before the packed row existed. Whole lanes a row are not
enough to keep a leaf where it lies: a prefill chunk's contractions over one
slot's strip re-laid both leaves WHOLE into their own orders on entry and
back on exit, a head a row too (Mistral's chunk, PR 64: four copies of 537
MB a chunk of any width), so ``cache_write`` holds whatever leaf it writes
to the row-major layout (``row_major``), whatever ``p`` is. The paged pool
(paged_kv.py) is not packed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from picotron_tpu.config import ModelConfig
from picotron_tpu.ops.attention import NEG_INF
from picotron_tpu.utils import on_tpu

# int8 symmetric range; scales are stored in fp32 so dequantization is one
# multiply with no double-rounding
INT8_MAX = 127.0
SCALE_DTYPE = jnp.float32
LANE = 128  # lanes of a TPU register row: what a leaf's row is made whole to


def cache_pspecs(quantized: bool = False, dp: int = 1) -> dict:
    """PartitionSpecs of the cache pytree: K/V head axis over 'tp', and —
    on a dp-sharded serving mesh (``dp > 1``) — the slot axis over 'dp',
    so each dp shard owns ``slots / dp`` contiguous slots of cache plus
    their length rows. ``dp == 1`` keeps the historical tp-only specs
    byte-identical. int8 caches add per-row scale tensors whose trailing
    head axis shards over 'tp' alongside the K/V heads they scale."""
    slot_ax = "dp" if dp > 1 else None
    kv = P(None, slot_ax, None, "tp", None)
    specs = {"k": kv, "v": kv,
             "lengths": P(slot_ax) if dp > 1 else P()}
    if quantized:
        scale = P(None, slot_ax, None, "tp")
        specs["k_scale"] = scale
        specs["v_scale"] = scale
    return specs


def pack_factor(head_dim: int, kv_heads: int) -> int:
    """Heads that share one lane row of a K/V leaf, given the head size
    and the kv heads ONE device holds (after 'tp')."""
    p = LANE // head_dim
    if head_dim < LANE and LANE % head_dim == 0 and kv_heads % p == 0:
        return p
    return 1


def pack_heads(x: jnp.ndarray, p: int) -> jnp.ndarray:
    """[..., H, D] -> [..., H / p, p * D]: the same bytes, ``p`` heads a
    row."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] // p, p * x.shape[-1]))


def unpack_heads(x: jnp.ndarray, head_dim: int) -> jnp.ndarray:
    """[..., H / p, p * D] -> [..., H, D], the inverse of ``pack_heads``."""
    return x.reshape(x.shape[:-2] + (-1, head_dim))


def kv_pack(cache: dict, head_dim: int):
    """Heads a row of this cache's K/V leaves holds, read off the leaf's
    shape; None for a cache without them (the latent cache)."""
    return cache["k"].shape[-1] // head_dim if "k" in cache else None


def init_cache(m: ModelConfig, slots: int, max_seq_len: int,
               dtype=None, quantized: bool = False, tp: int = 1) -> dict:
    """Zeroed global-shape cache for ``slots`` concurrent sequences on a
    mesh whose 'tp' axis is ``tp`` wide. Jit with out_shardings
    (engine.init_cache) to materialize each device's shard directly."""
    p = pack_factor(m.head_dim, m.num_key_value_heads // tp)
    per_head = (m.num_hidden_layers, slots, max_seq_len,
                m.num_key_value_heads)  # a scale a head, packed or not
    shape = per_head[:-1] + (per_head[-1] // p, p * m.head_dim)
    if quantized:
        cache = {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(per_head, SCALE_DTYPE),
            "v_scale": jnp.zeros(per_head, SCALE_DTYPE),
        }
    else:
        dt = jnp.dtype(dtype if dtype is not None else m.dtype)
        cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    cache["lengths"] = jnp.zeros((slots,), jnp.int32)
    return cache


# The latent cache (models/deepseek_v32.py): one row a token and layer and no
# head axis. ``ckv`` is the normalised compressed K/V every head expands
# from (kv_lora_rank wide) and, behind it in the same row, the one RoPE key
# all heads share (qk_rope_head_dim), zero-padded to whole lanes: the TPU
# lays out a leaf whose rows are not whole lanes (64 wide, or 576) with the
# tokens minor-most, and every program would copy it whole on its way in and
# out. ``ki`` is the sparse selection's indexer key (index_head_dim). Laid
# out ``[L, slots, T, width]`` like K and V, so the whole-cache ops below
# (``insert_prefill``, ``release``) and ``write_rows`` take it as it is;
# there is nothing for 'tp' to shard.
LATENT_LEAVES = ("ckv", "ki")


def latent_widths(m: ModelConfig) -> dict:
    return {"ckv": -(-(m.kv_lora_rank + m.qk_rope_head_dim) // LANE) * LANE,
            "ki": m.index_head_dim}


def init_latent_cache(m: ModelConfig, slots: int, max_seq_len: int,
                      dtype=None) -> dict:
    """Zeroed latent cache for ``slots`` concurrent sequences."""
    dt = jnp.dtype(dtype if dtype is not None else m.dtype)
    cache = {n: jnp.zeros((m.num_hidden_layers, slots, max_seq_len, w), dt)
             for n, w in latent_widths(m).items()}
    cache["lengths"] = jnp.zeros((slots,), jnp.int32)
    return cache


def cache_bytes(cache: dict) -> int:
    """Total bytes the cache pytree occupies (K/V + scales + lengths) —
    the HBM-budget metric the int8 mode halves."""
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))


# --------------------------------------------------------------------------- #
# int8 quantization
# --------------------------------------------------------------------------- #


def quantize_kv(x: jnp.ndarray) -> tuple:
    """Absmax-quantize rows of ``x`` [..., head_dim] to int8: one fp32
    scale per leading index (= per written row per kv head). A zero row
    quantizes to zeros with scale 0 — dequantization is exact there."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax / INT8_MAX
    q = jnp.round(xf / jnp.maximum(scale, 1e-12)[..., None])
    return jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8), scale


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    """Inverse of ``quantize_kv``: [..., D] int8 * [...] scale -> dtype."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def quantized(cache: dict) -> bool:
    """Whether a cache pytree (full or per-layer) stores int8 K/V."""
    return "k_scale" in cache


# --------------------------------------------------------------------------- #
# the write/attend seam (runs inside the engine's layer scan / shard_map)
# --------------------------------------------------------------------------- #
#
# Both take the cache dict the engine hands ``llama.decoder_layer``: the
# STACKED storage leaves ([L, ...], carried through the layer scan, never
# sliced out of it) plus a ``layer`` index, and whatever per-dispatch
# addressing entries the program spliced in — none of them a stored leaf:
# ``block_tables``/``page_quant`` (paged layout), ``draft_valid`` (ragged
# verify), ``slot`` (the one slot a B == 1 block addresses in a many-slot
# contiguous cache) and ``gate`` (a bool scalar: keep this block's rows or
# leave the bytes as they were).


def row_major(x: jnp.ndarray) -> jnp.ndarray:
    """Hold a carried cache leaf (or what is written into it) to the
    row-major layout it is resident in."""
    return with_layout_constraint(
        x, Layout(major_to_minor=tuple(range(x.ndim))))


def cache_write(cache: dict, k_new: jnp.ndarray, v_new: jnp.ndarray,
                pos: jnp.ndarray, layer) -> dict:
    """Write fresh K/V rows into ``layer`` of the stacked cache leaves, in
    place: only the new rows move (a scatter or a ``dynamic_update_slice``
    on the carried buffer — the layer's block is never sliced out and
    written back). Returns the dict with the updated leaves. Three shapes
    of write:

    - decode (``S == 1``): ``k_new``/``v_new`` [B, 1, H, D] with ``pos``
      [B] — every slot writes one row at its own position (a per-row
      scatter; free slots write their invisible row 0);
    - one slot's block (``B == 1`` and ``S > 1``, or a ``slot`` / ``gate``
      entry at any ``S`` — a prefill chunk, the mixed lane): [1, S, H, D]
      with ``pos`` [1] — the slot (``cache["slot"]``, default 0) writes a
      contiguous block of rows starting at ``pos[0]``. A ``gate`` entry
      (the dp owner / idle-lane gate) selects, row for row, between the
      new rows and the bytes already there. What picks this shape is the
      ADDRESSING, not the width: a chunk of one token still lands in its
      own slot and still honours its gate;
    - speculative verify (``S > 1``, ``B > 1``): [B, S, H, D] with ``pos``
      [B] — EVERY slot writes S contiguous rows starting at its own
      position (engine._verify_impl's optimistic draft write). Rows past
      the cache window drop (jax scatter out-of-bounds semantics — no
      clamping onto earlier rows), and rows past the post-acceptance
      length are stale: the length pointer is the rewind, ``attend``'s
      mask makes them unreachable (tests/test_speculative.py pins that a
      rejected draft leaves attention output identical to never having
      written it).

    int8 caches quantize on write; the scale rows land at the same
    positions in ``k_scale``/``v_scale``. The rows reach the leaf as it
    lies: ``p`` heads a row (``pack_heads``, a reshape of the new rows).
    The written ``k`` / ``v`` leaf is held to the row-major layout it is
    resident in (``row_major``), at every ``p`` and in every shape of
    write: a constraint on the layout alone, which moves no value, so that
    no program that attends over what it wrote (a prefill chunk's dense
    contractions above all) has the whole leaf re-laid around its loop.

    RAGGED verify (the per-slot spec_len controller): a ``draft_valid``
    [B] int32 entry (spliced per dispatch by engine._verify_impl) caps
    each slot's write at its own count of REAL fed tokens — rows at or
    past it are redirected out of the window and DROP under jax's
    out-of-bounds scatter semantics, so a short-drafting slot never parks
    another slot's pad junk. Only the batched scatter honors it (the
    verify shape); the B == 1 block branch writes its whole block as
    before (a one-slot verify's pad rows land beyond the post-acceptance
    length, stale and unreachable — the pre-ragged contract).

    Paged caches (``inference.kv_layout: "paged"`` — the dict carries
    ``block_tables``) route to the page-indirect scatter
    (inference/paged_kv.py): same three write shapes, rows land in pool
    pages instead of a contiguous strip (ragged rows hit the NULL page).
    """
    if "block_tables" in cache:
        from picotron_tpu.inference import paged_kv

        return paged_kv.cache_write(cache, k_new, v_new, pos, layer)
    out = dict(cache)
    out.pop("draft_valid", None)
    for name, sname, new in (("k", "k_scale", k_new), ("v", "v_scale", v_new)):
        if quantized(cache):
            vals, scales = quantize_kv(new)
            out[sname] = write_rows(cache, sname, scales, pos, layer)
        else:
            vals = new
        p = cache[name].shape[-1] // new.shape[-1]
        # held where it lies: left free, a prefill chunk's contractions
        # re-lay the WHOLE leaf on entry (K tokens-minor, V heads-major) and
        # back on exit, to write and read one slot's strip
        out[name] = row_major(
            write_rows(cache, name, pack_heads(vals, p), pos, layer))
    return out


def write_rows(cache: dict, name: str, vals: jnp.ndarray, pos: jnp.ndarray,
               layer) -> jnp.ndarray:
    """Stacked leaf ``name`` with ``vals`` [B, S, ...] written into
    ``layer`` in place, in whichever of ``cache_write``'s three shapes the
    addressing entries of ``cache`` (``slot``, ``gate``, ``draft_valid``)
    and the widths pick. Any leaf laid out ``[L, slots, T, ...]`` is
    written this way: K and V, their scales, the rows of a latent cache."""
    leaf = cache[name]
    valid = cache.get("draft_valid")
    slot, gate = cache.get("slot", 0), cache.get("gate")
    layer = jnp.asarray(layer, jnp.int32)
    B, S = vals.shape[0], vals.shape[1]
    T = leaf.shape[2]
    vals = vals.astype(leaf.dtype)
    if "slot" in cache or gate is not None or (B == 1 and S > 1):
        at = (layer, jnp.asarray(slot, jnp.int32),
              jnp.asarray(pos[0], jnp.int32))
        at += (jnp.zeros((), jnp.int32),) * (leaf.ndim - len(at))
        vals = vals[None]
        if gate is not None:
            vals = jnp.where(gate, vals,
                             lax.dynamic_slice(leaf, at, vals.shape))
        return lax.dynamic_update_slice(leaf, vals, at)
    if S == 1:
        return leaf.at[layer, jnp.arange(B), pos].set(vals[:, 0])
    rows = pos[:, None] + jnp.arange(S, dtype=pos.dtype)[None, :]
    if valid is not None:
        # ragged mask: rows past the slot's own real-token count
        # go out of bounds, where the scatter drops them
        cols = jnp.arange(S, dtype=jnp.int32)[None, :]
        rows = jnp.where(cols < valid[:, None], rows, T)
    return leaf.at[layer, jnp.arange(B)[:, None], rows].set(vals)


def layer_block(cache: dict, name: str, layer):
    """One layer's [B, T, ...] view of stacked leaf ``name`` — a read
    through an index, which the compiler fuses into whatever consumes it
    (the score and value contractions, the int8 dequantize); with a
    ``slot`` entry, that one slot's [1, T, ...] strip. None when the cache
    has no such leaf (the scales of an unquantized cache)."""
    leaf = cache.get(name)
    if leaf is None:
        return None
    leaf = lax.dynamic_index_in_dim(leaf, layer, 0, keepdims=False)
    if "slot" in cache:
        leaf = lax.dynamic_slice_in_dim(leaf, cache["slot"], 1, axis=0)
    return leaf


def plain_decode(q: jnp.ndarray, cache: dict, block: int = 1) -> bool:
    """Whether an ``attend`` call is the plain decode shape the stacked
    flash-decode kernel serves: one fresh query a slot (or, with ``block``
    > 1, one whole aligned block of ``block`` fresh rows a slot, every row
    of which sees every live key; or two such blocks, the first of which
    stops before the second's keys, where the kernel's form for two limits
    fits the heads, ``decode_attention.early_fits``) against a contiguous
    bfloat16 cache of whole-lane rows, every slot at its own length, with no
    addressing entry spliced in (``slot`` / ``gate``: one slot's block;
    ``draft_valid``: a ragged verify)."""
    k = cache.get("k")
    S = q.shape[1]
    if not (k is not None and k.ndim == 5
            and "block_tables" not in cache and not quantized(cache)
            and k.dtype == q.dtype == jnp.bfloat16
            and k.shape[-1] % LANE == 0
            and not any(n in cache for n in ("slot", "gate", "draft_valid"))):
        return False
    if S == 2 * block > 2:
        from picotron_tpu.ops.pallas.decode_attention import early_fits

        return early_fits(S * q.shape[2], k.shape[3])
    return S in (1, block)


def attend(q: jnp.ndarray, cache: dict, lengths: jnp.ndarray,
           scale: float, layer, impl: str = "dense",
           block: int = 1) -> jnp.ndarray:
    """Masked attention of S fresh queries against ``layer`` of the
    stacked cache leaves (``layer_block``: read where it lies).

    ``block`` is the width of the band a fresh row sees: keys up to the
    end of its own block of ``block`` positions, counted from the
    sequence's start, and never past the last fresh row (``block == 1``:
    up to itself, the causal band; ``models/sdar_moe.py``: bidirectional
    inside a block, causal between blocks). The fresh rows start on a block
    boundary where ``block`` > 1 (the caller's to hold: its window, chunk
    and round are whole blocks), so ``S == block`` rows a slot are one
    block, each sees every live key, and the call is the plain decode
    shape with ``S`` times the query heads (``plain_decode``); ``S == 2 x
    block`` rows are two blocks (a finished block in front of the one a
    round of blocks starts, ``engine._fused_forward``), still one call of
    that shape, whose first half stops ``block`` keys early
    (``_attend_whole_block``). The dense rule and that shape hold a band;
    the sliced flash kernel and the paged attends see the causal one alone
    and refuse another.

    ``impl`` picks the kernel (config ``inference.attend_impl``):

    - "auto" (the shipped default): on a TPU the plain decode shape
      (``plain_decode``) runs the stacked flash-decode kernel; every other
      call (prefill chunks, verify, the mixed lane, int8 and paged caches)
      and EVERY call off a TPU runs "dense". The choice is made at trace
      time from the call's shapes and the backend: no knob to set;
    - "dense": ``decode_attention`` over the whole cache window, int8
      storage first dequantized to a whole-block fp32 copy (the bit-pinned
      reference path);
    - "flash": the Pallas flash-decode kernels everywhere
      (ops/pallas/decode_attention.py; interpret mode off a TPU: the
      parity surface of tests/test_decode_kernel.py, ``chip_smoke.py`` and
      the described-chip compiles). The plain decode shape takes
      ``flash_decode_stacked``, as under "auto": K and V are read out of
      the stacked leaf where they lie, packed rows whole, one pass, live
      rows only. The other shapes take ``flash_decode_attention`` on the
      sliced layer, a head a row: KV blocks read only up to each slot's
      live length, int8 bytes + per-row scales travel to the kernel as
      stored and dequantize in registers, wide chunked-prefill query
      windows split over a q-block grid axis.

    Paged caches (the dict carries ``block_tables``) route to the
    page-indirect attends (inference/paged_kv.py): dense gathers the
    slots' pages into a contiguous window and runs the same masked
    einsum; flash walks the block table page by page in the kernel.
    """
    plain = plain_decode(q, cache, block)
    if impl == "auto":
        impl = "flash" if plain and on_tpu() else "dense"
    if "block_tables" in cache:
        from picotron_tpu.inference import paged_kv

        if block != 1:
            raise NotImplementedError("the paged attends see a causal band")
        return paged_kv.attend(q, cache, lengths, scale, layer, impl)
    if impl == "flash" and plain:
        from picotron_tpu.ops.pallas.decode_attention import (
            flash_decode_stacked,
        )

        if q.shape[1] == 1:
            return flash_decode_stacked(q, cache["k"], cache["v"], lengths,
                                        scale, layer, interpret=not on_tpu())
        return _attend_whole_block(q, cache, lengths, scale, layer, block)
    k, v, k_scale, v_scale = (layer_block(cache, n, layer)
                              for n in ("k", "v", "k_scale", "v_scale"))
    D = q.shape[-1]
    if impl == "flash":
        if block != 1:
            raise NotImplementedError(
                "the sliced flash kernel sees a causal band")
        # the kernel takes a head a row
        k, v = unpack_heads(k, D), unpack_heads(v, D)
        from picotron_tpu.ops.pallas.decode_attention import (
            flash_decode_attention,
        )

        return flash_decode_attention(
            q, k, v, lengths, scale, k_scale=k_scale, v_scale=v_scale,
            interpret=not on_tpu())
    if impl != "dense":
        # a typo'd impl must not silently measure the wrong kernel
        raise ValueError(f"unknown attend impl {impl!r} (auto|dense|flash)")
    if quantized(cache):
        # a scale a head: dequantize a head a row, hand the rows on packed
        k, v = (pack_heads(dequantize_kv(unpack_heads(x, D), s, jnp.float32),
                           x.shape[-1] // D)
                for x, s in ((k, k_scale), (v, v_scale)))
    return decode_attention(q, k, v, lengths, scale, block)


def _attend_whole_block(q, cache: dict, lengths, scale: float, layer,
                        block: int):
    """``attend``'s plain decode shape at ``S == block`` > 1: every one of a
    slot's S fresh rows sees every live key, so the rows ride beside the
    query heads of their cache row (``[B, S, rows, p, g, D] -> [B, 1, rows x
    p x S x g, D]``: ``S x g`` query heads a kv head) through the stacked
    kernel, which reads the live rows of K and V once for all of them, in
    the decode step's own K blocks: an ``S``-th of them (the step's score
    tile kept) made a call four times the grid steps, most of them past a
    slot's walk, and took half as long again (0.889 against 0.614 ms a layer
    at 5,000 tokens a slot, the step itself 0.569; PERF.md section 6, PR
    62).

    At ``S == 2 x block`` the rows are two blocks, and the fold is the same:
    the first block's ``block x g`` query heads of every kv head stop
    ``block`` keys before ``lengths`` (they see the stored prefix and
    themselves, not the second block), which the kernel holds as a static
    form of the same pass (``flash_decode_stacked``'s ``early``), so both
    blocks read the live keys once."""
    from picotron_tpu.ops.pallas.decode_attention import flash_decode_stacked

    B, S, nh, D = q.shape
    k, v = cache["k"], cache["v"]
    rows, p = k.shape[3], k.shape[4] // D
    g = nh // (rows * p)
    fold = q.reshape(B, S, rows, p, g, D).transpose(0, 2, 3, 1, 4, 5)
    out = flash_decode_stacked(
        fold.reshape(B, 1, S * nh, D), k, v, lengths, scale, layer,
        interpret=not on_tpu(),
        early=None if S == block else (block * g, block))
    out = out.reshape(B, rows, p, S, g, -1).transpose(0, 3, 1, 2, 4, 5)
    return out.reshape(B, S, nh, -1)


def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, scale: float,
                     block: int = 1) -> jnp.ndarray:
    """Masked dot-product attention of S fresh queries against a cache block.

    q: [B, S, n_heads, D] — the new tokens, the LAST of which sits at global
    position ``lengths[b] - 1`` (its K/V are already written); k/v:
    [B, T, n_kv_heads / p, p * D] cache blocks, ``p`` heads a row (read off
    the shapes; ``p == 1`` is [B, T, n_kv_heads, D]); lengths: [B] int32
    valid-key counts.
    GQA is handled natively by a grouped einsum over the compact kv heads —
    no repeat, no extra cache bytes. fp32 softmax with the same NEG_INF
    masking convention as ops/attention.py, output cast back to q.dtype.

    Packed rows (``p > 1``) are contracted whole, as they lie: the ``p * g``
    query heads of a row ride the group axis, each in its own head's ``D``
    lanes and zero in the others (``_own_lanes_only``), and of the
    ``p * D`` lanes the value contraction returns each keeps its own head's
    (``_own_lanes``). A lane multiplied by an exact zero adds an exact
    zero: the same products reach the same fp32 sums.

    S == 1 is the autoregressive decode step; S > 1 is chunked continuation
    — prefill chunks (B == 1) or speculative verify batches (B > 1)
    attending over the already-written prefix plus themselves (each query i
    masks keys past its own position; with ``block`` > 1, past the end of
    its own block of ``block`` positions, and past the last fresh row:
    ``attend``).
    """
    B, S, nh, D = q.shape
    T, nkv = k.shape[1], k.shape[2]  # nkv rows of p heads
    pack = k.shape[3] // D
    g = nh // nkv  # query heads a row: p * (heads a kv head)
    qg = q.reshape(B, S, nkv, g, D)
    if pack > 1:
        qg = _own_lanes_only(qg, pack)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    # query s has global position lengths - S + s; key t visible iff t <= it
    pos_q = lengths[:, None] - S + jnp.arange(S)[None, :]  # [B, S]
    if block > 1:  # ... iff t lies in its block or before it
        pos_q = jnp.minimum(pos_q // block * block + block - 1,
                            lengths[:, None] - 1)
    mask = jnp.arange(T)[None, None, :] <= pos_q[:, :, None]  # [B, S, T]
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.exp(scores - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    if pack > 1:
        out = _own_lanes(out, pack)
    return out.reshape(B, S, nh, D).astype(q.dtype)


def _own_lanes_only(qg: jnp.ndarray, p: int) -> jnp.ndarray:
    """[..., p * g, D] query heads of a packed row -> [..., p * g, p * D]:
    head ``j`` of the row's ``p`` kv heads (its ``g`` query heads) in lanes
    ``j * D .. (j + 1) * D``, exact zeros in the others."""
    *lead, pg, D = qg.shape
    own = jnp.eye(p, dtype=bool)[:, None, :, None]  # [p, 1, p, 1]
    spread = jnp.where(own, qg.reshape(*lead, p, pg // p, 1, D), 0)
    return spread.reshape(*lead, pg, p * D)


def _own_lanes(out: jnp.ndarray, p: int) -> jnp.ndarray:
    """The inverse selection on the value contraction's [..., p * g, p * D]:
    of each query head's ``p`` candidate blocks the one under its own kv
    head's lanes -> [..., p * g, D]."""
    *lead, pg, pD = out.shape
    blocks = out.reshape(*lead, p, pg // p, p, pD // p)
    return jnp.stack([blocks[..., j, :, j, :] for j in range(p)],
                     axis=-3).reshape(*lead, pg, pD // p)


# --------------------------------------------------------------------------- #
# whole-cache ops (host-facing, jitted by the engine)
# --------------------------------------------------------------------------- #


def insert_prefill(cache: dict, kv: dict, slot, length) -> dict:
    """Park a prefill's ``{"k","v"[,"k_scale","v_scale"]}:
    [L, 1, S_bucket, H, D(, )]`` blocks into ``slot`` and set its length
    (the engine's prefill already quantized the blocks for int8 caches).
    Rows past ``length`` (the bucket pad) are written but unreachable under
    the length mask. ``slot``/``length`` may be traced scalars — one
    compile per bucket size, not per slot."""
    slot = jnp.asarray(slot, jnp.int32)

    def put(name):
        dst, src = cache[name], kv[name].astype(cache[name].dtype)
        return lax.dynamic_update_slice(
            dst, src, (0, slot) + (0,) * (dst.ndim - 2))

    out = {name: put(name) for name in cache if name != "lengths"}
    out["lengths"] = cache["lengths"].at[slot].set(
        jnp.asarray(length, jnp.int32))
    return out


def release(cache: dict, slot) -> dict:
    """Free a slot: zero its length so no stale key is ever visible again.
    The K/V rows themselves stay — the next occupant overwrites what it
    needs and masks the rest."""
    return {**cache, "lengths": cache["lengths"].at[slot].set(0)}


# --------------------------------------------------------------------------- #
# the prefix store beside the strips (host half: paged_kv.PrefixStore)
# --------------------------------------------------------------------------- #
#
# A pool of pages for every storage leaf of the cache, ``[L, pages,
# page_len, ...]`` with the leaf's own row behind (a packed row stays packed:
# a page is ``page_len`` rows of a strip, byte for byte), page 0 the NULL
# page: what a copy reads from it nobody reads, what a copy writes to it
# nobody keeps. Both copies index the pool as ONE axis of ``L * pages``
# pages (a free reshape), a page of a layer an index: the form the chip's
# compiler gathers and scatters in place, with no layer sliced out and
# nothing re-laid (``paged_kv.gather_window``; tests/test_chip_compile.py
# reads both programs' compiled text).


def store_leaves(cache: dict) -> list:
    """The leaves a prefix store keeps pages of: every stacked leaf."""
    return [n for n in cache if n != "lengths"]


def init_store(shapes: dict, num_pages: int, page_len: int) -> dict:
    """Zeroed page pools shaped after the cache's leaves (``shapes``: the
    cache's own arrays or their abstract shapes)."""
    return {n: jnp.zeros(shapes[n].shape[:1] + (num_pages, page_len)
                         + shapes[n].shape[3:], shapes[n].dtype)
            for n in store_leaves(shapes)}


def _flat_pages(pool: jnp.ndarray, pids: jnp.ndarray) -> tuple:
    """(the pool as [L * pages, page_len, ...], the flat index [L, n] of
    page ``pids[i]`` in every layer)."""
    L, pages = pool.shape[:2]
    at = (jnp.arange(L, dtype=jnp.int32)[:, None] * pages
          + jnp.asarray(pids, jnp.int32)[None, :])
    return pool.reshape((L * pages,) + pool.shape[2:]), at


def retain_rows(store: dict, cache: dict, slot, pids) -> dict:
    """Retention's copy, strip -> pool: page ``i`` of ``slot``'s strip
    (rows ``[i * page_len, (i + 1) * page_len)`` of every leaf and layer)
    lands in pool page ``pids[i]``; ``pids`` [ceil(T / page_len)] int32
    names the NULL page wherever nothing is to be kept. ``store`` is
    donated and updated in place; the cache is only read."""
    slot = jnp.asarray(slot, jnp.int32)
    out = {}
    for name, pool in store.items():
        leaf = cache[name]
        n, page_len = pids.shape[0], pool.shape[2]
        at0 = (jnp.zeros((), jnp.int32),) * (leaf.ndim - 2)
        strip = lax.dynamic_slice(
            leaf, (at0[0], slot) + at0,
            leaf.shape[:1] + (1,) + leaf.shape[2:])[:, 0]  # [L, T, ...]
        short = n * page_len - strip.shape[1]
        if short:  # a window that ends inside its last page
            strip = jnp.pad(strip, ((0, 0), (0, short))
                            + ((0, 0),) * (strip.ndim - 2))
        flat, at = _flat_pages(pool, pids)
        pages = strip.reshape((-1, page_len) + strip.shape[2:])
        out[name] = flat.at[at.reshape(-1)].set(pages).reshape(pool.shape)
    return out


def seat_rows(cache: dict, store: dict, slot, pids, length) -> dict:
    """The hit's copy, pool -> strip: pool page ``pids[i]`` becomes page
    ``i`` of ``slot``'s strip in every leaf and layer, and the slot's
    length is set to ``length`` (the retained prefix, whole pages). The
    whole strip is written, rows past ``length`` with whatever the NULL
    page holds: they lie behind the length mask and the prompt's own chunks
    and decode steps overwrite them. ``cache`` is donated and updated in
    place; the store is only read."""
    slot = jnp.asarray(slot, jnp.int32)
    out = dict(cache)
    for name, pool in store.items():
        leaf = cache[name]
        flat, at = _flat_pages(pool, pids)
        rows = flat[at]  # [L, n, page_len, ...]
        rows = rows.reshape(rows.shape[:1] + (-1,) + rows.shape[3:])
        rows = rows[:, None, : leaf.shape[2]]
        at0 = (jnp.zeros((), jnp.int32),) * (leaf.ndim - 2)
        out[name] = lax.dynamic_update_slice(leaf, rows,
                                             (at0[0], slot) + at0)
    out["lengths"] = cache["lengths"].at[slot].set(
        jnp.asarray(length, jnp.int32))
    return out


def live_tokens(cache: dict) -> jax.Array:
    """Total tokens currently parked across slots (occupancy metric for
    the batcher/bench)."""
    return jnp.sum(cache["lengths"])
