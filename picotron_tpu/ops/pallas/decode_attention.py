"""Flash decode: fused KV-cache attention as a Pallas TPU kernel.

The serving counterpart of ``flash_attention.py``. The dense decode path
(``inference/kv_cache.py::decode_attention``) attends every fresh query
against the **whole** ``[B, max_seq_len, Hkv, D]`` cache block — an einsum
whose HBM traffic is O(max_seq_len) per decoded token no matter how short
the live sequences are, and whose int8 mode first materializes a
dequantized fp32 copy of the entire block (4x the bytes the cache stores).
This kernel removes both costs:

- **Length-aware**: the grid is ``(slots, q_blocks, kv_blocks)`` and the
  K/V BlockSpec index maps read the slot's ``lengths`` entry (a
  scalar-prefetch operand in SMEM): grid step ``j`` fetches KV block
  ``min(j, nb - 1)`` where ``nb = ceil(visible / block_t)`` is the slot's
  OWN live block count clipped to the highest key its query rows can see
  (the causal block-skip of the training flash kernel, shared via
  ``flash_attention.causal_kv_blocks``). A repeated block index is not
  fetched again by the Pallas pipeline and steps ``j >= nb`` skip their
  compute, so HBM reads track parked tokens, not the cache window (an
  empty slot still costs its block 0: the pipeline always fetches the
  first block of a grid row). Keys inside the last partial block are
  masked per query row against ``lengths`` (the stale rows a speculative
  rollback or a freed slot leaves beyond the length pointer are never
  visible).
- **int8 dequant on the score tile**: K/V stay int8 on the wire — each
  block arrives in its storage dtype together with its per-row fp32
  scales (``[block_t, Hkv]``) and the scales multiply the
  ``[Hkv, rows, block_t]`` scores / probabilities (``q·(s·k) == s·(q·k)``
  per key row), so the quantized cache's ~2x byte saving reaches the
  attend itself and no dequantized K/V block ever exists.
- **Every kv head per grid instance**: a KV block is the whole
  ``[block_t, Hkv, D]`` slab. The ``(Hkv, D)`` plane is the tiled minor
  plane of the cache in HBM and the TPU compiler refuses a one-head slice
  of it, so heads are a batch dimension of the two contractions instead
  of a grid axis; queries fold to ``[B, Hkv, S*g, D]`` (``g = Hq/Hkv``
  grouped rows per compact kv head) and the cache stays compact, nothing
  is repeated.
- **S >= 1 queries per slot**: query row ``r`` sits at global position
  ``pos_q = lengths[b] - S + r // g`` (key ``t`` visible iff
  ``t <= pos_q``) — the exact masking convention of the dense kernel — so
  ONE kernel serves all three call sites: blocked decode (S = 1),
  speculative verify (S = spec_len + 1, B = slots), and chunked prefill
  (B = 1, S = chunk width).
- **Blocked queries for chunked prefill**: wide query groups split over
  the second grid axis; each q-block walks only the KV blocks its own
  causal band can see.

Softmax is the standard online (flash) recurrence in fp32: running max
``m``, normalizer ``l``, and accumulator ``acc`` per query row live in
VMEM scratch across the kv grid axis, masked probabilities zeroed exactly
so a fully-masked row (``lengths == 0`` — a fresh slot attended directly)
comes out as **zeros**, a defined value, where the dense kernel emits an
(equally unconsumed) uniform average. Every other row is allclose to the
dense path for bf16/fp32 AND int8 caches (tests/test_decode_kernel.py
pins all three call shapes in interpret mode; tests/test_chip_compile.py
compiles every layout for a v5e at SmolLM widths; chip_smoke.py runs it
compiled against the dense oracle).

Block fetches are the Pallas pipeline's own double-buffered DMA, but in the
dense form of ``flash_decode_stacked``, which copies the blocks it walks
itself (PR 63). On CPU the kernels run in Pallas interpret mode
(``interpret=True``): the parity suite's and the tier-1 gate's path.

``flash_decode_stacked`` (PR 36, at the end of the file) is the kernel the
shipped default ``inference.attend_impl: auto`` runs on a TPU for the plain
decode step (``S == 1``, a contiguous bfloat16 cache of whole-lane rows): it
takes the STACKED leaves and a layer index, so nothing is sliced or unpacked
in front of it, contracts a block's (token, cache row) pairs against every
query head in one matmul, and walks live blocks only: a slot a grid step,
its blocks a loop inside it (``_dense_kernel``; a ring: ``_stacked_kernel``).
``flash_decode_attention`` keeps every other shape under ``attend_impl:
flash`` (verify, prefill chunks, int8, paged) and takes one layer's sliced
block, a head a row; ``dense`` serves those under ``auto``.

``block_tables`` switches to the PAGED layout (one block per pool page,
the page id read from the block table in the index map);
``block_quant`` additionally enables the **mixed-precision page read**
(``inference.kv_page_policy: "hot_bf16"`` — inference/paged_kv.py): each
page carries a per-page flag choosing which of the two pool
representations to fetch — the full-precision leaves for hot
(radix-shared) prefix pages, the int8+scales leaves for cold unique
tails. The representation a page does NOT use maps to pool page 0, so a
run of same-kind pages costs the other pool one fetch, not one per page.

**The program_id rule (picolint PICO-J003).** ``pl.program_id`` is read
ONCE at the top of the kernel, never inside a loop or ``pl.when`` body:
the Pallas interpreter every CPU test runs (jax 0.9.0 too) has no lowering
for it inside a ``fori_loop`` body's sub-jaxpr, so the kernel would
compile for the chip and fail on the interpret path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.ops.attention import NEG_INF
from picotron_tpu.ops.pallas.flash_attention import (
    _dot_nt,
    _pick_block,
    _scale_folds,
    causal_kv_blocks,
)

# KV rows per block; halved automatically until the block divides the
# cache window, the [Hkv*block_q, block_t] fp32 score tile stays under
# _MAX_SCORE_TILE elements, and one [block_t, Hkv, D] slab stays under
# _MAX_KV_SLAB bytes (see _pick_block_t / _kv_block_cap).
DEFAULT_BLOCK_T = 256
# Folded query rows (S*g) per grid instance, summed over kv heads.
# Decode/verify shapes fold into one block; chunked-prefill windows wider
# than this split over the q grid axis instead of shrinking block_t.
DEFAULT_BLOCK_Q = 256
# score-tile budget: 256K fp32 elements = 1 MB, the same tile scale the
# training flash kernel's 512x512 default occupies
_MAX_SCORE_TILE = 256 * 1024
_SUBLANE = 8  # fp32 sublane quantum the padded query-row count respects
# one K or V slab in VMEM (all kv heads of one KV block); x2 operands x2
# pipeline buffers stays a quarter of the 16 MB scoped VMEM
_MAX_KV_SLAB = 1024 * 1024


def _pick_block_t(seq: int, want: int, rows: int = _SUBLANE) -> int:
    """KV block size: at or under ``want``, shrunk (a) so the
    ``[rows, block]`` fp32 score tile fits the VMEM budget and (b) by
    halving until it divides ``seq`` (flash_attention._pick_block — the
    block must tile the cache window exactly; this is what keeps windows
    that are NOT a multiple of the preferred block correct instead of
    reading past the buffer)."""
    while want > _SUBLANE and rows * want > _MAX_SCORE_TILE:
        want //= 2
    return _pick_block(seq, want)


def _kv_block_cap(want: int, nkv: int, d: int, itemsize: int) -> int:
    """Cap the KV block so one ``[block, Hkv, D]`` slab (D padded to the
    128-lane tile in VMEM) stays within _MAX_KV_SLAB bytes."""
    row = nkv * max(d, 128) * itemsize
    while want > _SUBLANE and want * row > _MAX_KV_SLAB:
        want //= 2
    return want


def _pick_block_q(sgp: int, want: int, block_t: int) -> int:
    """Folded query rows per grid instance: at or under ``want``, dividing
    the padded row count, shrunk until the [rows, block_t] fp32 score tile
    fits the VMEM budget (the paged layout fixes block_t at the page
    length, so rows are the only tunable there)."""
    rq = _pick_block(sgp, want)
    while rq > _SUBLANE and rq * block_t > _MAX_SCORE_TILE:
        rq = _pick_block(sgp, rq // 2)
    return rq


def _visible_blocks(L, qi, *, S, g, rq, block_t, max_nb):
    """KV blocks q-tile ``qi`` of a slot with ``L`` live tokens walks.
    Clipped twice: (a) to the highest key the tile's causal band can see
    (early chunked-prefill q-blocks never walk the whole window), and (b)
    to the window's block count: at the window edge the engine's
    write-then-attend convention can pass lengths = pos + S > T (the
    scatter dropped the out-of-bounds rows), and the walk must not read
    past the cache. Shared by the index maps and the kernel body, so the
    block a step fetches is the block it computes on."""
    hi = jnp.clip(L - S + (qi * rq + rq - 1) // g, -1, L - 1)
    return jnp.maximum(causal_kv_blocks(max_nb, hi, block_t), 0)


def _flash_decode_kernel(*refs, scale, block_t, S, g, rq, max_nb, quantized,
                         mixed, paged):
    """One (slot, q-block, kv-block) grid step: ``rq`` folded query rows
    of slot ``b`` under EVERY kv head against KV block ``j`` of the slot's
    walk — already steered to the right cache rows (or pool page, or pool
    representation) by the scalar-prefetch index maps. The online-softmax
    state rides in VMEM scratch across the ``j`` axis."""
    refs = list(refs)
    len_ref = refs.pop(0)
    if paged:
        refs.pop(0)  # block tables: consumed by the index maps
    qt_ref = refs.pop(0) if mixed else None
    q_ref = refs.pop(0)
    k_ref = refs.pop(0)
    v_ref = refs.pop(0)
    kq_ref = refs.pop(0) if mixed else None
    vq_ref = refs.pop(0) if mixed else None
    scaled = quantized or mixed
    ks_ref = refs.pop(0) if scaled else None
    vs_ref = refs.pop(0) if scaled else None
    o_ref, acc_ref, m_ref, l_ref = refs
    # program ids are read ONCE here, outside every pl.when body (picolint
    # PICO-J003 — see the module docstring)
    b = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    L = len_ref[b]  # this slot's live token count
    nb = _visible_blocks(L, qi, S=S, g=g, rq=rq, block_t=block_t,
                         max_nb=max_nb)
    isq = (qt_ref[b, jnp.minimum(j, max_nb - 1)] != 0) if mixed else None

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _block(ref, qref, sref):
        """This step's K or V slab as fp32 ``[block_t, Hkv, D]`` plus its
        per-row scales as a ``[Hkv, 1, block_t]`` factor (None: unscaled)."""
        if mixed:
            blk = jnp.where(isq, qref[0].astype(jnp.float32),
                            ref[0].astype(jnp.float32))
            return blk, jnp.where(isq, sref[0].T[:, None, :], 1.0)
        blk = ref[0].astype(jnp.float32)
        return blk, (sref[0].T[:, None, :] if quantized else None)

    @pl.when(j < nb)
    def _():
        q = q_ref[0].astype(jnp.float32)  # [Hkv, rq, D]
        # query row r = s*g + g_idx sits at global position L - S + s
        pos_q = (L - S + (qi * rq + lax.broadcasted_iota(
            jnp.int32, (rq, block_t), 0)) // g)
        kpos = j * block_t + lax.broadcasted_iota(
            jnp.int32, (rq, block_t), 1)
        mask = (kpos <= pos_q)[None]
        kb, ks = _block(k_ref, kq_ref, ks_ref)
        s = jnp.einsum("hqd,thd->hqt", q, kb,
                       preferred_element_type=jnp.float32) * scale
        if ks is not None:
            s = s * ks  # dequant on the score tile
        s = jnp.where(mask, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=2, keepdims=True))
        # zero masked probabilities EXACTLY (not just exp(-inf)): a row
        # whose every key so far is masked keeps l == 0 and lands on the
        # defined all-zeros output below instead of a uniform average
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=2, keepdims=True)
        m_ref[...] = m_new
        vb, vs = _block(v_ref, vq_ref, vs_ref)
        if vs is not None:
            p = p * vs
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "hqt,thd->hqd", p, vb, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        o_ref[0] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


def flash_decode_attention(q, k, v, lengths, scale, *,
                           k_scale=None, v_scale=None,
                           k_quant=None, v_quant=None,
                           block_quant=None,
                           block_t: int | None = None,
                           block_q: int | None = None,
                           block_tables=None,
                           interpret: bool = False):
    """Fused masked attention of S fresh queries per slot against a KV
    cache block, reading only live rows.

    q: [B, S, n_heads, D] — the new tokens, the LAST of which sits at
    global position ``lengths[b] - 1``; k/v: [B, T, n_kv_heads, D] cache
    blocks, int8 when ``k_scale``/``v_scale`` ([B, T, n_kv_heads] fp32
    per-row scales) are given; lengths: [B] int32 valid-key counts.
    Returns [B, S, n_heads, D] in q.dtype — allclose to
    ``kv_cache.decode_attention`` on every query row with at least one
    visible key (``pos_q = lengths[b] - S + s >= 0``; inside the engine
    that is every row of every occupied slot). Fully-masked rows —
    ``lengths == 0``, or the leading rows of a direct call with
    ``lengths < S`` — return ZEROS, where the dense kernel emits an
    equally-unconsumed uniform average over the whole window.
    ``interpret=True`` runs the Pallas interpreter (the CPU path).

    ``block_tables`` ([B, max_pages] int32) switches to the PAGED cache
    layout (inference/paged_kv.py): k/v (and scales) are then the global
    page pool — ``[num_pages, page_len, n_kv_heads, D]`` — and slot
    ``b``'s walk reads pool page ``block_tables[b, j]`` at iteration
    ``j`` instead of its contiguous block ``j``. The KV block size is
    the page length; everything else (masking, online softmax, GQA fold,
    in-register dequant) is the identical code path.

    ``k_quant``/``v_quant`` + ``block_quant`` ([B, max_pages] int32, paged
    only) enable the MIXED-precision page read (``kv_page_policy:
    "hot_bf16"``): k/v stay the full-precision pool, k_quant/v_quant are
    the parallel int8 pool with ``k_scale``/``v_scale`` per-row scales,
    and page ``j`` of slot ``b`` is fetched from whichever representation
    ``block_quant[b, j]`` selects (0 = full precision, nonzero = int8).

    ``block_q`` caps the folded query rows per grid instance (chunked
    prefill splits wide windows over the q grid axis)."""
    B, S, nh, D = q.shape
    paged = block_tables is not None
    mixed = k_quant is not None
    if mixed != (v_quant is not None):
        raise ValueError("k_quant and v_quant must be given together")
    if mixed and not paged:
        raise ValueError(
            "mixed-precision pages (k_quant/v_quant) require the paged "
            "layout (block_tables)")
    if mixed and block_quant is None:
        raise ValueError(
            "mixed-precision pages need block_quant per-page flags")
    if paged:
        if block_tables.shape[0] != B:
            raise ValueError(
                f"block_tables rows {block_tables.shape[0]} != batch {B}")
        T = block_tables.shape[1] * k.shape[1]  # max_pages * page_len
        nkv = k.shape[2]
    else:
        T, nkv = k.shape[1], k.shape[2]
    if nh % nkv:
        raise ValueError(f"n_heads {nh} not a multiple of n_kv_heads {nkv}")
    quantized = (k_scale is not None) and not mixed
    if (k_scale is not None) != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be given together")
    if mixed and k_scale is None:
        raise ValueError("mixed-precision pages need k_scale/v_scale for "
                         "the int8 representation")
    if (k.dtype == jnp.int8) != quantized:
        raise ValueError(
            f"int8 cache blocks need per-row scales (and vice versa); got "
            f"k.dtype={k.dtype} with scales="
            f"{'set' if k_scale is not None else 'unset'}")
    g = nh // nkv
    sg = S * g
    sgp = -(-sg // _SUBLANE) * _SUBLANE  # pad query rows to the sublane tile
    # every grid instance holds ALL kv heads of its rows, so the score
    # tile is [nkv, rq, bt]: both pickers budget nkv*rq rows. paged: the
    # block IS a whole pool page, so the q-block count is the only
    # VMEM-budget tunable there
    want_q = max(_SUBLANE, (block_q or DEFAULT_BLOCK_Q) // nkv)
    if paged:
        bt = k.shape[1]
        rq = _pick_block_q(sgp, want_q, nkv * bt)
        max_nb = block_tables.shape[1]
    else:
        rq = _pick_block(sgp, want_q)
        bt = _pick_block_t(T, _kv_block_cap(block_t or DEFAULT_BLOCK_T,
                                            nkv, D, k.dtype.itemsize),
                           rows=nkv * rq)
        max_nb = T // bt
    # fold [B, S, nkv, g, D] -> [B, nkv, S*g, D]: one kv head's whole query
    # group per row block (tiny copy — S is 1..chunk, never the cache)
    qf = q.reshape(B, S, nkv, g, D).swapaxes(1, 2).reshape(B, nkv, sg, D)
    if sgp != sg:
        qf = jnp.pad(qf, ((0, 0), (0, 0), (0, sgp - sg), (0, 0)))

    # lengths, block tables and page flags ride as scalar-prefetch
    # operands: whole arrays in SMEM, read by the index maps and the
    # kernel (a per-slot (1,) SMEM block is refused by the TPU lowering)
    prefetch = [lengths.astype(jnp.int32)]
    if paged:
        prefetch.append(block_tables.astype(jnp.int32))
    if mixed:
        prefetch.append(block_quant.astype(jnp.int32))

    def _walk(b, i, j, len_ref):
        """Block index grid step j fetches: the walk's own block while it
        lasts, then the last one again (a repeat costs no DMA)."""
        nb = _visible_blocks(len_ref[b], i, S=S, g=g, rq=rq, block_t=bt,
                             max_nb=max_nb)
        return jnp.clip(jnp.minimum(j, nb - 1), 0, max_nb - 1)

    def kv_map(want_quant=None):
        """Index map of a K/V (or scale) operand. ``want_quant`` (mixed
        mode): the page-flag value under which THIS pool representation is
        the one read; otherwise the fetch parks on pool page 0."""
        def index(b, i, j, len_ref, *tables):
            jj = _walk(b, i, j, len_ref)
            if not paged:
                return (b, jj)
            page = tables[0][b, jj]
            if want_quant is not None:
                page = jnp.where((tables[1][b, jj] != 0) == want_quant,
                                 page, 0)
            return (page, 0)
        return index

    def kv_spec(arr, want_quant=None):
        rest = arr.shape[2:]  # (Hkv, D) for K/V, (Hkv,) for scales
        imap = kv_map(want_quant)
        return pl.BlockSpec(
            (1, bt) + rest,
            lambda *a: imap(*a) + (0,) * len(rest))

    q_spec = pl.BlockSpec((1, nkv, rq, D), lambda b, i, j, *_: (b, 0, i, 0))
    full = False if mixed else None
    in_specs = [q_spec, kv_spec(k, full), kv_spec(v, full)]
    operands = [qf, k, v]
    if mixed:
        in_specs += [kv_spec(k_quant, True), kv_spec(v_quant, True)]
        operands += [k_quant, v_quant]
    if quantized or mixed:
        sq = True if mixed else None
        in_specs += [kv_spec(k_scale, sq), kv_spec(v_scale, sq)]
        operands += [k_scale, v_scale]

    kernel = functools.partial(
        _flash_decode_kernel, scale=float(scale), block_t=bt, S=S, g=g,
        rq=rq, max_nb=max_nb, quantized=quantized, mixed=mixed, paged=paged)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, sgp // rq, max_nb),
            in_specs=in_specs,
            out_specs=q_spec,
            scratch_shapes=[pltpu.VMEM((nkv, rq, D), jnp.float32),
                            pltpu.VMEM((nkv, rq, 1), jnp.float32),
                            pltpu.VMEM((nkv, rq, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, nkv, sgp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode_attention",
    )(*prefetch, *operands)
    return (out[:, :, :sg]
            .reshape(B, nkv, S, g, D).swapaxes(1, 2)
            .reshape(B, S, nh, D))


# --------------------------------------------------------------------------- #
# the plain decode step: K and V read out of the stacked leaf, where they lie
# --------------------------------------------------------------------------- #

# one K or V block of the stacked kernel (x2 operands x2 pipeline buffers:
# a quarter of the 16 MB scoped VMEM, beside a [heads, block] fp32 score tile)
_STACKED_KV_BLOCK = 1024 * 1024
_BF16_ROWS = 16  # rows of a bf16 register tile: what the query rows pad to


def _stacked_block_rows(T: int, row_bytes: int) -> int:
    """Tokens a K block of the stacked kernel holds by default: as many as
    stay under ``_STACKED_KV_BLOCK`` where that count divides the strip's
    ``T`` rows (every row of whole powers of two: the Llama cells', the
    Trinity cell's), else the largest divisor of ``T`` under it in whole
    bfloat16 tiles of 16 (a row of 768 or 1,536 lanes gives 682 and 341,
    which ``_pick_block`` would halve down to 2 and 5), else what
    ``_pick_block`` finds."""
    cap = min(T, max(_SUBLANE, _STACKED_KV_BLOCK // row_bytes))
    return cap if T % cap == 0 else _tile_divisor(T, cap)


def _tile_divisor(T: int, cap: int) -> int:
    """The largest divisor of ``T`` up to ``cap`` in whole bfloat16 tiles of
    16 rows, else what ``_pick_block`` finds."""
    fits = [b for b in range(_BF16_ROWS, cap + 1, _BF16_ROWS) if T % b == 0]
    return max(fits) if fits else _pick_block(T, cap)


def _ring_block_rows(T: int, row_bytes: int, window: int) -> int:
    """Tokens a K block of the ring form holds by default: the largest
    divisor of the ring's ``T`` rows in whole bfloat16 tiles that is at most
    the window and at most ``_stacked_block_rows``' (a block larger than the
    window crosses the HBM with rows no query sees, whatever the walk skips:
    a ring of 640 rows under a window of 128 goes in blocks of 128, one of
    4,608 under 4,096 in the 512 it had)."""
    return _tile_divisor(T, min(_stacked_block_rows(T, row_bytes), window))


def _stacked_blocks(L, block_t, max_nb):
    """KV blocks a slot with ``L`` live tokens walks at ``S == 1``
    (``_visible_blocks`` for one query row at position ``L - 1``)."""
    return _visible_blocks(L, 0, S=1, g=1, rq=1, block_t=block_t,
                           max_nb=max_nb)


def _ring_walk(window: int, block_t: int, max_nb: int) -> int:
    """The most blocks of ``block_t`` rows, of a ring's ``max_nb``, that a
    window's rows touch wherever they start: the grid's second extent in the
    ring form."""
    seen = min(window, max_nb * block_t)
    return min(max_nb, -(-(seen - 1) // block_t) + 1)


def _ring_blocks(written, last, *, window, block_t, max_nb):
    """``(first, count)``: the blocks of a ring that hold a row its query
    sees, as the one holding the oldest such row and how many there are from
    it on, the ring's end joined to its start (step ``j`` of the walk is
    block ``_ring_block(first, j, max_nb)``). ``written`` rows of the ring
    are live and the query's own key lies in row ``last``, so it sees the
    ``min(written, window)`` rows that end there. Shared by the index maps
    and the kernel body, as ``_stacked_blocks`` is: the block a step fetches
    is the block it masks. A free slot (``written == 0``) walks none and
    costs the block of ``last``."""
    n = jnp.minimum(written, window)
    oldest = last - jnp.maximum(n, 1) + 1
    oldest = jnp.where(oldest < 0, oldest + max_nb * block_t, oldest)
    # nothing here is negative: lax.div and lax.rem spare the scalar core
    # the sign fix of ``//`` and ``%`` in every index map of every step
    count = lax.div(lax.rem(oldest, block_t) + n + (block_t - 1), block_t)
    count = jnp.where(n > 0, jnp.minimum(count, max_nb), 0)
    return lax.div(oldest, block_t), count


def _ring_block(first, j, max_nb):
    """Block number of step ``j < max_nb`` of a walk from block ``first``
    (a compare and a subtract where ``%`` is a division by no power of two
    on the scalar core, in every index map of every grid step)."""
    blk = first + j
    return jnp.where(blk >= max_nb, blk - max_nb, blk)


def _stacked_kernel(len_ref, layer_ref, last_ref, *refs, scale, block_t, rows,
                    pg, max_nb, steps, window, sink=False):
    """One (slot, kv-block) grid step of ``flash_decode_stacked``'s ring form
    (``window``; the dense form's ``_dense_kernel`` keeps this arithmetic and
    walks a slot's blocks inside one step). The K block is ``block_t``
    tokens of one layer and slot as a plain matrix:
    ``[block_t * rows, lanes]``, a row of it one (token, cache row) pair,
    every cache row of a token one after the other as the leaf holds them.
    ALL query heads meet ALL of those pairs in one NT matmul
    (``[heads, lanes] . [block_t * rows, lanes]^T``: K and V are the MXU's
    resident operand exactly as they arrive, no slice, no relayout), and a
    query head keeps the pairs of its own cache row (``own_ref``: the
    column's token offset where the rows match, past every bound where
    they do not). The other rows' products are exact discards: masked to
    NEG_INF before the softmax, an exact zero in the value matmul.

    The slot's strip is a ring of ``max_nb`` blocks and the third scalar
    operand names the row the query's own key lies in: a row is seen if it
    is live and fewer than ``window`` rows behind that one, the ring's end
    joined to its start. The grid's second axis is the walk over the blocks
    that hold such a row (``_ring_blocks``: step ``j`` of the grid's
    ``steps`` is block ``first + j`` round the ring), not over the blocks
    that are written: a block wholly out of the window is never fetched. The
    order the blocks come in is nothing to a running softmax.

    With a ``sink`` a fourth operand holds one float32 a query row: the
    running softmax starts from it (its maximum, a denominator of exp(0),
    nothing accumulated), so it takes its share of every row's mass and has
    no value. V's lanes need not be K's: the accumulator and the output are
    as wide as V."""
    del layer_ref  # consumed by the index maps
    q_ref, refs = refs[0], refs[1:]
    sink_ref, refs = (refs[0], refs[1:]) if sink else (None, refs)
    k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, own_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    L = len_ref[b]
    first, nb = _ring_blocks(L, last_ref[b], window=window,
                             block_t=block_t, max_nb=max_nb)
    blk = _ring_block(first, j, max_nb)
    nq, cols = own_ref.shape

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if sink:
            m_ref[...] = sink_ref[...]
            l_ref[...] = jnp.ones_like(l_ref)
        else:
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
        head_row = lax.broadcasted_iota(jnp.int32, (nq, cols), 0) // pg
        col = lax.broadcasted_iota(jnp.int32, (nq, cols), 1)
        own_ref[...] = jnp.where(col % rows == head_row, col // rows,
                                 jnp.iinfo(jnp.int32).max)

    @pl.when(j < nb)
    def _():
        s = _dot_nt(q_ref[...], k_ref[...])
        if scale is not None:
            s = s * scale
        # token blk * block_t + own is visible iff it is below the length
        seen = own_ref[...] < L - blk * block_t
        # and, of a ring, fewer than ``window`` rows behind the query's
        age = last_ref[b] - blk * block_t - own_ref[...]
        seen &= jnp.where(age < 0, age + max_nb * block_t, age) < window
        s = jnp.where(seen, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        # every walked block holds a visible key for every head (of a
        # prefix token blk * block_t of its own row, of a ring a row of the
        # window), so m_new is finite and a masked score's exp underflows
        # to an exact zero
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)

    @pl.when(j == steps - 1)
    def _():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l > 0, l, 1.0)
        o_ref[...] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


def early_fits(heads: int, rows: int) -> bool:
    """Whether ``flash_decode_stacked`` takes ``heads`` query heads against
    ``rows`` cache rows a token in its ``early`` form: the rows pair up, and
    a pair's query heads are whole bfloat16 tiles."""
    return rows % 2 == 0 and heads % rows == 0 \
        and heads // rows * 2 % _BF16_ROWS == 0


def flash_decode_stacked(q, k, v, lengths, scale, layer, *,
                         block_t: int | None = None,
                         interpret: bool = False,
                         window: int | None = None, sink=None,
                         early: tuple | None = None):
    """The ``S == 1`` decode attend of a contiguous, unquantized cache,
    reading layer ``layer`` of the STACKED leaves in place: one pass over
    K and V, live rows only.

    q: [B, 1, n_heads, D]; k/v: the carried leaves ``[L, B, T, rows,
    p * D]`` as ``kv_cache`` lays them out (``p`` kv heads side by side in
    a row of whole lanes; ``p == 1``: a head a row); lengths: [B] int32
    valid-key counts (the query sits at ``lengths - 1``); ``layer`` a
    traced or static index. Returns [B, 1, n_heads, D] in q.dtype,
    allclose to ``kv_cache.decode_attention`` on the layer's block; a slot
    with ``lengths == 0`` returns ZEROS (module docstring). V's heads may
    be narrower than K's (``v``: ``[L, B, T, rows, p * Dv]``; the output
    is then [B, 1, n_heads, Dv]), and ``sink`` [n_heads] float32 is a
    learned logit a query head that joins its softmax and has no value
    (``models/mimo_v2.py``); the forms without either are the programs they
    were, and the compiled kernel's name tells the forms apart
    (``flash_decode_attention`` | ``flash_decode_ring``, ``_sink`` behind
    either).

    ``early`` = ``(n, by)`` (static; the dense form's alone) gives a call two
    limits a slot: of each kv head's query heads the first ``n`` see ``by``
    keys fewer than ``lengths`` says, the rest all of them. It is how two
    blocks of fresh rows folded beside the query heads attend in one pass
    over the keys (``kv_cache._attend_whole_block``: the first block's rows
    stop before the second block's keys). The limits differ in the walk's
    last one or two blocks; the difference rides the tile of token offsets
    the kernel fills at its first step, and without ``early`` nothing that
    is traced changes. With it the call scores a pair of cache rows at a
    time (``_dense_kernel``), which takes ``early_fits``.

    ``layer`` and ``lengths`` are scalar-prefetch operands and the leaves
    are viewed as ``[L, B, T * rows, p * D]`` (the same bytes: tokens and
    rows merge into one major axis, which the compiled program shows as a
    bitcast): no layer is sliced out, no head unpacked, no copy stands in
    front of the custom call. A slot walks ``ceil(lengths[b] / block_t)``
    blocks and a call pays for those alone (``_dense_kernel``: the grid is
    the slots, a slot's live blocks a loop inside its step, the next block,
    the next slot's first at a slot's end, in flight while one is scored); a
    free slot fetches nothing.

    With a ``window`` a slot's strip is a ring (``models/afmoe.py``: position
    ``p`` lies in row ``p mod T``) and the walk is over the blocks that hold
    a row the query SEES, live and fewer than ``window`` behind its own, the
    ring's end joined to its start (``_ring_blocks``), not over the blocks
    that are written: the grid's second extent is the most blocks a window
    can touch (``_ring_walk``), the index map returns the ``j``-th seen
    block, and past the walk the last one again (no DMA, the step's compute
    skipped). The default block is no larger than the window
    (``_ring_block_rows``), since whatever a block holds beside the window's
    rows crosses the HBM to be masked: a ring of 640 rows under a window of
    128 is read as one or two blocks of 128 where blocks of 320 read it
    whole. ``block_t`` overrides either default.

    Packed rows are contracted whole, as ``kv_cache.decode_attention``
    does it: the ``p * g`` query heads of a cache row each sit in their
    own head's lanes, exact zeros in the others, and of the ``p * D``
    lanes the value matmul returns each keeps its own (``_own_lanes``).
    The kernel itself sees ``rows`` heads of ``p * D`` lanes with ``p * g``
    query rows each and has no notion of packing. Scores, softmax
    statistics and the accumulator are fp32; K meets q in the leaf's dtype
    and the probabilities are rounded to it for the value matmul (what the
    chip's default matmul precision makes of the dense path's fp32
    probabilities)."""
    from picotron_tpu.inference.kv_cache import _own_lanes, _own_lanes_only

    B, S, nh, D = q.shape
    if S != 1:
        raise ValueError(f"flash_decode_stacked is the S == 1 step, got {S}")
    nl, _, T, rows, lanes = k.shape
    pack, lanes_v = lanes // D, v.shape[-1]
    if nh % rows or lanes != pack * D or v.shape[:-1] != k.shape[:-1] \
            or lanes_v % pack:
        raise ValueError(
            f"{nh} query heads of {D} against rows {rows} x {lanes} lanes "
            f"of K, {v.shape[-2]} x {lanes_v} of V")
    if q.dtype != k.dtype:
        raise ValueError(f"q is {q.dtype}, the cache leaves {k.dtype}")
    pg = nh // rows  # query heads a cache row: p * (heads a kv head)
    if early is not None and (window is not None or sink is not None
                              or not 0 < early[0] < pg // pack
                              or not early_fits(nh, rows)
                              or k.dtype != jnp.bfloat16):
        raise ValueError(
            f"early {early}: the dense form's, without a sink, fewer than a "
            f"kv head's {pg // pack} query heads, bfloat16 leaves (two cache "
            f"rows a 32-bit word) and ``early_fits``")
    qg = q.reshape(B, rows, pg, D)
    if pack > 1:
        qg = _own_lanes_only(qg, pack)
    qm = qg.reshape(B, nh, lanes)
    if _scale_folds(scale):
        qm, scale = qm * jnp.asarray(scale, qm.dtype), None
    nq = -(-nh // _BF16_ROWS) * _BF16_ROWS
    if nq != nh:  # pad rows own no cache row: all masked, sliced off below
        qm = jnp.pad(qm, ((0, 0), (0, nq - nh), (0, 0)))
    row_bytes = rows * lanes * k.dtype.itemsize
    if block_t:
        bt = _pick_block(T, block_t)
    elif window is None:
        bt = _stacked_block_rows(T, row_bytes)
    else:
        bt = _ring_block_rows(T, row_bytes, window)
    cols, max_nb = bt * rows, T // bt
    lengths = lengths.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def own(out):  # [B, nq, lanes_v] -> the heads and lanes the query came in
        out = out[:, :nh].reshape(B, 1, rows, pg, lanes_v)
        if pack > 1:
            out = _own_lanes(out, pack)
        return out.reshape(B, 1, nh, lanes_v // pack)

    def operands():
        """The query rows, a sink a row, the leaves' tokens and rows merged."""
        ops = [qm]
        if sink is not None:
            # a float32 a query row; the pad rows' is 0 (``own`` slices it off)
            ops.append(jnp.pad(sink.astype(jnp.float32),
                               (0, nq - nh)).reshape(nq, 1))
        return ops + [k.reshape(nl, B, T * rows, lanes),
                      v.reshape(nl, B, T * rows, lanes_v)]

    if window is None:
        return own(_dense_call(
            lengths, layer, operands(), scale=scale, block_t=bt, rows=rows,
            pg=pg, interpret=interpret,
            early=early and (pg // pack, *early)))
    steps = _ring_walk(window, bt, max_nb)
    # rows written, and the row of the query's own key
    prefetch = (jnp.minimum(lengths, T), layer, (lengths - 1) % T)

    def kv_index(b, j, len_ref, layer_ref, last_ref):
        first, nb = _ring_blocks(len_ref[b], last_ref[b],
                                 window=window, block_t=bt, max_nb=max_nb)
        jj = jnp.maximum(jnp.minimum(j, nb - 1), 0)  # past the walk: no DMA
        jj = _ring_block(first, jj, max_nb)
        return (layer_ref[0], b, jj, 0)

    def row_spec(width):  # a slot's query rows, or what they come to
        return pl.BlockSpec((None, nq, width), lambda b, j, *_: (b, 0, 0))

    def kv_spec(width):
        return pl.BlockSpec((None, None, cols, width), kv_index)

    in_specs = [row_spec(lanes)]
    if sink is not None:
        in_specs.append(pl.BlockSpec((nq, 1), lambda b, j, *_: (0, 0)))
    return own(pl.pallas_call(
        functools.partial(_stacked_kernel, scale=scale, block_t=bt,
                          rows=rows, pg=pg, max_nb=max_nb, steps=steps,
                          window=window, sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(B, steps),
            in_specs=in_specs + [kv_spec(lanes), kv_spec(lanes_v)],
            out_specs=row_spec(lanes_v),
            scratch_shapes=[pltpu.VMEM((nq, lanes_v), jnp.float32),
                            pltpu.VMEM((nq, 1), jnp.float32),
                            pltpu.VMEM((nq, 1), jnp.float32),
                            pltpu.VMEM((nq, cols), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, nq, lanes_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_decode_ring" + ("" if sink is None else "_sink"),
    )(*prefetch, *operands()))


# --------------------------------------------------------------------------- #
# the dense form: a slot a grid step, its live blocks walked inside the kernel
# --------------------------------------------------------------------------- #


def _dense_parts(block_t: int, rows: int) -> int:
    """Pieces a K block of the dense form is copied in: four, or two, where
    a piece is whole tokens and whole bfloat16 tiles of 16 rows, else one."""
    return next((n for n in (4, 2) if block_t % n == 0
                 and block_t // n * rows % _BF16_ROWS == 0), 1)


def _dense_kernel(len_ref, layer_ref, *refs, scale, block_t, rows, pg, max_nb,
                  parts, sink, early=None):
    """Grid step ``b`` of the dense form: slot ``b``'s live K and V blocks,
    ``_stacked_blocks(lengths[b])`` of them and no other, in
    ``_stacked_kernel``'s arithmetic (a block as a plain matrix, every head
    against every (token, cache row) pair, a head keeps its own row's), so
    what a call pays follows the blocks that are live: a slot with two of
    twelve runs two rounds of the loop, a free slot none.

    K and V stay in HBM and a block comes into one of two VMEM buffers a
    leaf by a copy of this kernel's own: while block ``j`` is scored, block
    ``j + 1`` is in flight, and while a slot's last block is scored, the next
    slot's first (a free slot hands the request on), so only the call's
    first block is waited for with nothing to do. ``turn_ref`` carries the
    buffer the next block goes to from one grid step to the next; the tile of
    token offsets depends on the shapes alone and is filled at the call's
    first step.

    ``early`` = ``(of, n, by)``: of every ``of`` query rows (a kv head's) the
    first ``n`` see ``by`` keys fewer. Their tokens' offsets stand ``by``
    higher in the tile, so the one comparison with the length masks both
    kinds of row, in whichever of the walk's blocks the two limits part (a
    row that sees nothing of a block keeps the maximum it came with, and the
    block's probabilities are exact zeros for it). Such a call holds twice
    the query rows, and scoring every row against every (token, cache row)
    pair of a block would cost more than the block's copy (5,473 bundles a
    block of 1,024 tokens x 4 rows at 256 query rows, 4.0 us against 2.56;
    PERF.md section 6, PR 66). So it scores by PAIRS of cache rows: a
    bfloat16 buffer read as 32-bit words holds two neighbouring cache rows
    a word, rows ``2 i`` and ``2 i + 1`` of a token are word row ``i``, and
    a strided read of the words takes a pair's rows of every token out of
    the block ([2 x block_t, lanes]); the pair's query rows meet those
    alone, half of their scores their own where a quarter were (3,203
    bundles, 2.3 us: under the copy again). The tile of offsets is then a
    pair's, [2 x pg, 2 x block_t], the same for every pair."""
    q_ref, refs = refs[0], refs[1:]
    sink_ref, refs = (refs[0], refs[1:]) if sink else (None, refs)
    k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, own_ref, turn_ref = refs
    b = pl.program_id(0)
    last_slot = pl.num_programs(0) - 1
    layer = layer_ref[0]
    L = len_ref[b]
    nb = _stacked_blocks(L, block_t, max_nb)
    after = jnp.minimum(b + 1, last_slot)
    # is there a first block to ask for when this slot's walk ends
    follows = (b < last_slot) & (len_ref[after] > 0)
    nq, cols = q_ref.shape[0], k_buf.shape[1]
    # the query rows are scored whole against a block, or a pair of cache
    # rows at a time against the pair's rows of it (``early``)
    groups = 1 if early is None else rows // 2
    nqg = nq // groups
    if early is not None:
        words = [buf.bitcast(jnp.uint32) for buf in (k_buf, v_buf)]

    def block(leaf, buf, i):
        """Block ``buf`` of ``leaf`` as group ``i``'s query rows meet it."""
        if early is None:
            return (k_buf, v_buf)[leaf][buf]
        pair = words[leaf][buf, pl.ds(i, block_t, stride=groups)]
        return pltpu.bitcast(pair, k_buf.dtype)

    piece = cols // parts  # rows of K a copy moves

    def copies(leaf, slot, blk, buf, act):
        """``act`` on the copy of each piece of block ``blk`` of ``slot`` that
        holds a live token (the first always does), into buffer ``buf`` of
        ``leaf``: ``_dense_parts`` pieces a block, so a slot's last block
        crosses the HBM up to its length's piece and not whole."""
        hbm, vmem = ((k_hbm, k_buf), (v_hbm, v_buf))[leaf]
        live = jnp.minimum(len_ref[slot] - blk * block_t, block_t) * rows
        for i in range(parts):
            copy = pltpu.make_async_copy(
                hbm.at[layer, slot, pl.ds(blk * cols + i * piece, piece)],
                vmem.at[buf, pl.ds(i * piece, piece)], sem.at[leaf, buf])
            if i == 0:
                act(copy)
            else:
                pl.when(live > i * piece)(functools.partial(act, copy))

    def fetch(slot, blk, buf):
        for leaf in (0, 1):
            copies(leaf, slot, blk, buf, lambda copy: copy.start())

    def wait(leaf, blk, buf):
        copies(leaf, b, blk, buf, lambda copy: copy.wait())

    @pl.when(b == 0)
    def _():
        head = lax.broadcasted_iota(jnp.int32, own_ref.shape, 0)
        head_row = head // pg
        col = lax.broadcasted_iota(jnp.int32, own_ref.shape, 1)
        if early is None:
            own, token = col % rows == head_row, col // rows
        else:  # a pair's tile: two cache rows a token, the early heads' later
            of, n, by = early
            own = col % 2 == head_row
            token = col // 2 + jnp.where(head % of < n, by, 0)
        own_ref[...] = jnp.where(own, token, jnp.iinfo(jnp.int32).max)
        turn_ref[0] = 0
        if parts > 1:
            # a piece never copied is masked, but its V rows still meet a
            # probability of exact zero: they must be numbers
            v_buf[...] = jnp.zeros_like(v_buf)

        @pl.when(nb > 0)
        def _():
            fetch(0, 0, 0)

    turn = turn_ref[0]
    q = q_ref[...]

    def score(j, carry):
        buf = (turn + j) & 1
        ends = j == nb - 1

        @pl.when(jnp.logical_not(ends) | follows)
        def _():
            fetch(jnp.where(ends, after, b), jnp.where(ends, 0, j + 1),
                  1 - buf)

        wait(0, j, buf)
        scored = []
        for i, (m, l, acc) in enumerate(carry):
            s = _dot_nt(q if groups == 1 else q[i * nqg:(i + 1) * nqg],
                        block(0, buf, i))
            if scale is not None:
                s = s * scale
            # token j * block_t + own is visible iff it is below the length
            s = jnp.where(own_ref[...] < L - j * block_t, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            # every walked block holds a visible key for every head (token
            # j * block_t of its own row), so m_new is finite and a masked
            # score's exp underflows to an exact zero
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            scored.append((m_new, l, alpha, acc, p))
        wait(1, j, buf)
        return tuple(
            (m_new, l, acc * alpha + jnp.dot(
                p.astype(v_buf.dtype), block(1, buf, i),
                preferred_element_type=jnp.float32))
            for i, (m_new, l, alpha, acc, p) in enumerate(scored))

    if sink:
        m0, l0 = sink_ref[...], jnp.ones((nq, 1), jnp.float32)
    else:
        m0 = jnp.full((nqg, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((nqg, 1), jnp.float32)
    done = lax.fori_loop(0, nb, score, (
        (m0, l0, jnp.zeros((nqg, o_ref.shape[1]), jnp.float32)),) * groups)
    l, acc = (jnp.concatenate(x) if groups > 1 else x[0]
              for x in ([g[1] for g in done], [g[2] for g in done]))

    @pl.when((nb == 0) & follows)  # a free slot hands the request on
    def _():
        fetch(after, 0, turn)

    turn_ref[0] = (turn + nb) & 1
    out = acc / jnp.where(l > 0, l, 1.0)
    o_ref[...] = jnp.where(l > 0, out, 0.0).astype(o_ref.dtype)


def _dense_call(lengths, layer, operands, *, scale, block_t, rows, pg,
                interpret, early=None):
    """The dense form's ``pallas_call``: ``operands`` are the query rows
    ``[B, nq, lanes]``, a sink a row ``[nq, 1]`` or nothing, and the K and V
    leaves as ``[L, B, T * rows, lanes]``, which stay where they are
    (``pl.ANY``: the kernel copies what it walks); returns ``[B, nq,
    lanes_v]``. The grid is the slots, in order on one core: a step leaves
    the next one's first block in flight."""
    qm, k, v = operands[0], operands[-2], operands[-1]
    (B, nq, lanes), lanes_v = qm.shape, v.shape[-1]
    cols, sink = block_t * rows, len(operands) == 4

    def row_spec(width):  # a slot's query rows, or what they come to
        return pl.BlockSpec((None, nq, width), lambda b, *_: (b, 0, 0))

    in_specs = [row_spec(lanes)]
    if sink:
        in_specs.append(pl.BlockSpec((nq, 1), lambda b, *_: (0, 0)))
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    return pl.pallas_call(
        functools.partial(_dense_kernel, scale=scale, block_t=block_t,
                          rows=rows, pg=pg, max_nb=k.shape[2] // cols,
                          parts=_dense_parts(block_t, rows), sink=sink,
                          early=early),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=in_specs,
            out_specs=row_spec(lanes_v),
            scratch_shapes=[pltpu.VMEM((2, cols, lanes), k.dtype),
                            pltpu.VMEM((2, cols, lanes_v), v.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((nq, cols) if early is None
                                       else (2 * pg, 2 * block_t), jnp.int32),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, nq, lanes_v), qm.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="flash_decode_attention" + ("_sink" if sink else ""),
    )(lengths, layer, *operands)
