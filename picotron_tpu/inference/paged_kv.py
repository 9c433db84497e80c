"""Paged KV cache: a global page pool with refcounted prefix sharing + COW.

The contiguous cache (inference/kv_cache.py) gives every slot its own
``max_seq_len`` strip, so HBM capacity is ``slots x max window`` no matter
how short the live sequences are — and two requests with the same system
prompt each prefill and store their own copy of it. This module replaces
the strip with **block-table indirection** over a global pool of
fixed-size KV pages (vLLM's PagedAttention layout) and builds **radix
prefix sharing** on top (SGLang's RadixAttention):

- **Device layout** (``init_cache``): the per-layer cache leaves become a
  page pool ``k``/``v``: ``[num_layers, num_pages, page_len, n_kv_heads,
  head_dim]`` (int8 mode adds ``k_scale``/``v_scale``
  ``[L, P, page_len, Hkv]`` exactly like the contiguous layout), plus
  ``block_tables [slots, max_pages_per_slot] int32`` mapping each slot's
  logical page index to a pool page, and the same ``lengths [slots]``.
  Page 0 is the reserved NULL page: unallocated table entries point at it
  and every out-of-window or masked write is redirected into it, so a
  bad index can scribble only on bytes nothing ever reads.
- **Host allocator** (``PagePool`` / ``PagedKV``): a free list plus a
  refcount per page. A page's refcount is the number of holders — each
  slot whose block table points at it, plus the radix cache when the page
  backs a cached prefix. Slots allocate lazily as their sequences grow
  (``ensure_writable``), release returns every held page
  (refcount-aware), and a write into a page with refcount > 1 first
  **copies-on-write**: the writer gets a fresh copy (``copy_page``, a
  byte-exact device copy) and drops its reference, so shared bytes are
  immutable for as long as anyone shares them.
- **Prefix sharing** (``RadixCache``): a trie over page-sized token
  chunks. After a prompt prefills, its prompt pages are inserted (the
  cache takes a reference); a new request walks the trie, reuses the
  pages of its longest cached prefix (bumping refcounts — zero prefill
  work for those tokens), and prefills only the suffix. The match may
  end mid-page (a fork point): the request shares the tail page too, and
  its first write past the fork triggers the COW above. Refcount-1
  leaves (held by nobody but the cache) are evicted LRU-first when the
  pool runs dry.

Correctness contract: K/V rows at position ``p`` depend only on tokens
``0..p`` (causal attention; the chunked-prefill overlap re-feed already
relies on this), so a cached page whose token path matches a request's
prompt prefix holds exactly the bytes that request's own prefill would
have written — sharing changes WHERE bytes live, never what they are.
The attend paths consume the indirection without changing math: the
dense path gathers the slot's pages into a contiguous window and runs
the same masked einsum (bit-identical — masked columns contribute exact
zeros), the flash kernel walks ``block_tables[b, i]`` pages instead of
contiguous blocks (ops/pallas/decode_attention.py). Selected by
``inference.kv_layout: "paged"``; tests/test_paged_kv.py pins paged
generations against contiguous across every dispatch family.
"""

from __future__ import annotations

import heapq
from collections import deque

import jax.numpy as jnp
import numpy as np
from jax import lax

from picotron_tpu.config import ModelConfig
from picotron_tpu.inference import kv_cache

# table entries start here; page 0 is the reserved NULL page (never
# allocated, the target of masked/out-of-window writes)
NULL_PAGE = 0


class PagePoolExhausted(RuntimeError):
    """No free page and nothing evictable — the caller sheds, it never
    corrupts a live slot."""


# --------------------------------------------------------------------------- #
# device ops (jitted by the engine)
# --------------------------------------------------------------------------- #


def cache_pspecs(quantized: bool = False, policy: bool = False,
                 dp: int = 1) -> dict:
    """PartitionSpecs of the paged cache pytree: identical to the
    contiguous layout's (the kv-head axis of the pool — and of the int8
    scale tensors — shards over 'tp'; page axes are replicated at dp=1),
    plus ``block_tables``. On a dp-sharded serving mesh (``dp > 1``) the
    POOL PAGE axis shards over 'dp' — each dp shard owns
    ``num_pages / dp`` pages holding only its own slots' K/V — and the
    per-slot ``block_tables``/``lengths`` rows shard with their slots.
    The ``hot_bf16`` policy adds the int8 side pool (``k_q``/``v_q`` +
    scales, same sharding) and the per-page ``page_quant`` flags."""
    from jax.sharding import PartitionSpec as P

    slot_ax = "dp" if dp > 1 else None
    specs = kv_cache.cache_pspecs(quantized, dp=dp)
    specs["block_tables"] = P(slot_ax, None) if dp > 1 else P()
    if policy:
        kv = P(None, slot_ax, None, "tp", None)
        scale = P(None, slot_ax, None, "tp")
        specs.update(k_q=kv, v_q=kv, k_scale=scale, v_scale=scale,
                     page_quant=P(slot_ax) if dp > 1 else P())
    return specs


# cache leaves with no layer axis: host-owned page metadata that rides as
# a scan constant through the engine's layer scan and is skipped by every
# per-page device op (copy_page slices the page axis, which these lack)
META_LEAVES = ("lengths", "block_tables", "page_quant")


def is_policy(cache: dict) -> bool:
    """Whether a cache pytree (full or per-layer) carries the hot_bf16
    dual-representation pool."""
    return "k_q" in cache


def init_cache(m: ModelConfig, slots: int, num_pages: int, page_len: int,
               max_pages: int, dtype=None, quantized: bool = False,
               policy: bool = False) -> dict:
    """Zeroed page pool + NULL block tables + zero lengths. Same dtype
    rules as the contiguous ``kv_cache.init_cache``. ``policy`` (the
    ``hot_bf16`` per-page policy) adds the int8 side pool: every write
    lands in BOTH representations and the per-page ``page_quant`` flag —
    refreshed from the host allocator's refcounts before each dispatch —
    selects which one the attend READS, so a page can flip between hot
    (full precision) and cold (int8) as sharing changes without ever
    rewriting bytes. (This reference implementation keeps both
    representations resident; a hardware allocator would partition one
    arena and demote pages physically — staged exactly like the dense/
    contiguous serving defaults.)"""
    shape = (m.num_hidden_layers, num_pages, page_len,
             m.num_key_value_heads, m.head_dim)
    if quantized:
        if policy:
            raise ValueError(
                "hot_bf16 page policy is mutually exclusive with a "
                "uniformly int8 cache (config.validate names the fix)")
        cache = {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], kv_cache.SCALE_DTYPE),
            "v_scale": jnp.zeros(shape[:-1], kv_cache.SCALE_DTYPE),
        }
    else:
        dt = jnp.dtype(dtype if dtype is not None else m.dtype)
        cache = {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
        if policy:
            cache.update({
                "k_q": jnp.zeros(shape, jnp.int8),
                "v_q": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], kv_cache.SCALE_DTYPE),
                "v_scale": jnp.zeros(shape[:-1], kv_cache.SCALE_DTYPE),
                "page_quant": jnp.zeros((num_pages,), jnp.int32),
            })
    cache["block_tables"] = jnp.full((slots, max_pages), NULL_PAGE,
                                     jnp.int32)
    cache["lengths"] = jnp.zeros((slots,), jnp.int32)
    return cache


def _targets(bt: jnp.ndarray, rows: jnp.ndarray, page_len: int):
    """Map logical row positions to (pool page, in-page offset) through a
    block table. ``bt`` [..., max_pages], ``rows`` [..., S] global
    positions. Rows outside the paged window redirect to the NULL page at
    offset 0 (mirroring the contiguous scatter's drop semantics — those
    rows are never visible either way)."""
    maxp = bt.shape[-1]
    valid = (rows >= 0) & (rows < maxp * page_len)
    page_idx = jnp.clip(rows // page_len, 0, maxp - 1)
    pid = jnp.take_along_axis(bt, page_idx, axis=-1)
    pid = jnp.where(valid, pid, NULL_PAGE)
    off = jnp.where(valid, rows % page_len, 0)
    return pid, off


def cache_write(cache: dict, k_new: jnp.ndarray, v_new: jnp.ndarray,
                pos: jnp.ndarray, layer) -> dict:
    """Paged counterpart of ``kv_cache.cache_write``: scatter each slot's
    S fresh rows through its block-table row into ``layer`` of the stacked
    pool leaves, in place. One generic gather+scatter
    serves all three write shapes (decode S=1, verify B>1 S>1, chunked
    prefill B=1 S=C) — row ``pos[b] + s`` lands in pool page
    ``bt[b, (pos+s) // page_len]`` at offset ``(pos+s) % page_len``.
    Out-of-window rows (and free slots' NULL table entries) write the
    NULL page. int8 caches quantize on write exactly like the contiguous
    path. The host allocator guarantees every page this can touch is
    exclusively owned by the writing slot (COW ran before the dispatch),
    so a shared page's bytes are never mutated — including by the
    speculative verify's optimistic writes that a rollback later strands.

    A ``draft_valid`` [B] int32 entry (the ragged-verify mask, spliced
    per dispatch — see kv_cache.cache_write) caps each slot's write at
    its own real-token count: masked rows are pushed out of the logical
    window, which ``_targets`` routes to the NULL page.
    """
    out = dict(cache)
    valid = out.pop("draft_valid", None)
    layer = jnp.asarray(layer, jnp.int32)
    B, S = k_new.shape[0], k_new.shape[1]
    bt = cache["block_tables"]  # [B, max_pages] int32
    page_len = cache["k"].shape[2]
    rows = pos[:, None].astype(jnp.int32) + jnp.arange(S, dtype=jnp.int32)
    if valid is not None and S > 1:
        cols = jnp.arange(S, dtype=jnp.int32)[None, :]
        rows = jnp.where(cols < valid[:, None], rows,
                         bt.shape[-1] * page_len)
    at = (layer,) + _targets(bt, rows, page_len)  # (layer, pid, off)

    def put(name, vals):
        out[name] = cache[name].at[at].set(vals.astype(cache[name].dtype))

    for name, qname, sname, new in (("k", "k_q", "k_scale", k_new),
                                    ("v", "v_q", "v_scale", v_new)):
        if is_policy(cache):
            # hot_bf16 dual write: the fresh rows land in BOTH pool
            # representations (full precision + int8 with scales), so the
            # per-page flag can flip as sharing changes without rewriting
            # bytes — the read side (attend) selects per page. Write
            # traffic is S rows per dispatch, noise next to the attend's
            # window read the policy halves.
            qvals, scales = kv_cache.quantize_kv(new)
            put(name, new)
            put(qname, qvals)
            put(sname, scales)
        elif kv_cache.quantized(cache):
            vals, scales = kv_cache.quantize_kv(new)
            put(name, vals)
            put(sname, scales)
        else:
            put(name, new)
    return out


def gather_window(pool: jnp.ndarray, bt: jnp.ndarray,
                  layer) -> jnp.ndarray:
    """Materialize slots' logical windows from ``layer`` of the stacked
    pool: ``pool`` [L, P, page_len, ...] + ``bt`` [B, max_pages] ->
    [B, max_pages * page_len, ...] — the contiguous view the dense
    reference attend consumes. The pages are gathered by ONE flat index
    ``layer * P + page`` over the pool seen as [L * P, page_len, ...] (a
    free reshape of the leading axes): the only form of three the chip's
    compiler leaves alone at every query width — slicing the layer first
    lands it in a buffer of its own each layer, and a two-coordinate
    gather of the stacked pool is re-laid out whole under a wide query
    window (PERF.md, PR 26). (The flash kernel never materializes this;
    it walks the table page by page.)"""
    L, P = pool.shape[:2]
    flat = pool.reshape((L * P,) + pool.shape[2:])
    g = flat[jnp.asarray(layer, jnp.int32) * P + bt]
    return g.reshape((bt.shape[0], -1) + g.shape[3:])


def attend(q: jnp.ndarray, cache: dict, lengths: jnp.ndarray,
           scale: float, layer, impl: str = "dense") -> jnp.ndarray:
    """Masked attention of S fresh queries against ``layer`` of the paged
    cache. "dense" gathers the slots' pages into a contiguous window and
    runs the bit-pinned ``kv_cache.decode_attention`` (int8 first
    dequantizes the gathered window to fp32, the same reference
    discipline as contiguous dense); "flash" hands the layer's pool +
    block tables to the Pallas kernel, which DMAs pages straight from HBM
    — no gathered window ever exists on that path."""
    bt = cache["block_tables"]
    policy = is_policy(cache)
    if impl == "flash":
        from picotron_tpu.ops.pallas.decode_attention import (
            flash_decode_attention,
        )
        from picotron_tpu.utils import on_tpu

        def pool(name):
            return kv_cache.layer_block(cache, name, layer)

        if policy:
            # mixed-precision page read: the per-page flag — gathered
            # through the block table into [B, max_pages] SMEM rows —
            # decides which pool representation each page's DMA fetches
            return flash_decode_attention(
                q, pool("k"), pool("v"), lengths, scale,
                k_quant=pool("k_q"), v_quant=pool("v_q"),
                k_scale=pool("k_scale"), v_scale=pool("v_scale"),
                block_tables=bt,
                block_quant=jnp.take(cache["page_quant"], bt, axis=0),
                interpret=not on_tpu())
        return flash_decode_attention(
            q, pool("k"), pool("v"), lengths, scale,
            k_scale=pool("k_scale"), v_scale=pool("v_scale"),
            block_tables=bt, interpret=not on_tpu())
    if impl != "dense":
        raise ValueError(f"unknown attend impl {impl!r} (dense|flash)")

    def window(name):
        return gather_window(cache[name], bt, layer)

    k, v = window("k"), window("v")
    if policy:
        # mixed dense read (the bit-pinned reference for the flash DMA
        # path above): gather both representations' windows, dequantize
        # the int8 one, and select per page — rows of a flagged page come
        # from the quantized bytes, exactly what the kernel DMAs
        page_len = k.shape[1] // bt.shape[1]
        flags = jnp.repeat(jnp.take(cache["page_quant"], bt, axis=0),
                           page_len, axis=1)  # [B, max_pages*page_len]
        quant = (flags != 0)[..., None, None]
        kq = kv_cache.dequantize_kv(window("k_q"), window("k_scale"),
                                    jnp.float32)
        vq = kv_cache.dequantize_kv(window("v_q"), window("v_scale"),
                                    jnp.float32)
        k = jnp.where(quant, kq, k.astype(jnp.float32))
        v = jnp.where(quant, vq, v.astype(jnp.float32))
    elif kv_cache.quantized(cache):
        k = kv_cache.dequantize_kv(k, window("k_scale"), jnp.float32)
        v = kv_cache.dequantize_kv(v, window("v_scale"), jnp.float32)
    return kv_cache.decode_attention(q, k, v, lengths, scale)


def insert_prefill(cache: dict, kv: dict, slot, length) -> dict:
    """Park a one-shot prefill's ``[L, 1, S_bucket, H, D]`` blocks into
    ``slot``'s pages and set its length — the paged ``insert``. Pad rows
    beyond ``length`` (and rows whose page was never allocated) write the
    NULL page. ``slot``/``length`` may be traced — one compile per bucket
    size, like the contiguous path."""
    slot = jnp.asarray(slot, jnp.int32)
    length = jnp.asarray(length, jnp.int32)
    bt = cache["block_tables"]
    row = lax.dynamic_slice_in_dim(bt, slot, 1, axis=0)  # [1, max_pages]
    S = kv["k"].shape[2]
    page_len = cache["k"].shape[2]
    rows = jnp.arange(S, dtype=jnp.int32)[None, :]  # [1, S]
    rows = jnp.where(rows < length, rows, -1)  # pad rows -> NULL page
    pid, off = _targets(row, rows, page_len)
    pid, off = pid[0], off[0]  # [S]

    def put(name):
        dst = cache[name]
        src = kv[name][:, 0].astype(dst.dtype)  # [L, S, ...]
        return dst.at[:, pid, off].set(src)

    out = {name: put(name) for name in cache if name not in META_LEAVES}
    for name in META_LEAVES:
        if name in cache:
            out[name] = cache[name]
    out["lengths"] = cache["lengths"].at[slot].set(length)
    return out


def slice_page(cache: dict, pid) -> dict:
    """One pool page's storage leaves as ``[L, page_len, ...]`` arrays —
    the single-page read (tests/debug). ``pid`` may be a traced scalar:
    one compiled executable serves every page, exactly like
    ``copy_page``."""
    pid = jnp.asarray(pid, jnp.int32)
    return {name: lax.dynamic_slice_in_dim(a, pid, 1, axis=1)[:, 0]
            for name, a in cache.items() if name not in META_LEAVES}


def gather_pages(cache: dict, pids: jnp.ndarray) -> dict:
    """A batch of pool pages' storage leaves as ``[n, L, page_len, ...]``
    arrays (page-major, matching ``write_pages``' input) — the export
    half of the page transport in ONE dispatch + ONE host sync, however
    long the prefix. The caller pads ``pids`` to a pow-2 bucket with
    NULL-page entries (free reads of bytes nothing cares about), so a
    handful of compiled shapes serve every export size."""
    pids = jnp.asarray(pids, jnp.int32)
    return {name: jnp.moveaxis(jnp.take(a, pids, axis=1), 1, 0)
            for name, a in cache.items() if name not in META_LEAVES}


def write_pages(cache: dict, pages: dict, pids: jnp.ndarray) -> dict:
    """Write a batch of imported pages' storage leaves into pool pages
    ``pids`` — the import half of the page transport, ONE dispatch per
    import. ``pages[name]`` is ``[n, L, page_len, ...]`` (page-major so
    the host stacks payload pages directly); ``pids`` is ``[n]`` int32.
    The caller pads ``n`` to a pow-2 bucket with NULL-page targets —
    page 0 is the designated scribble target nothing ever reads — so a
    handful of compiled shapes serve every import size. Byte-exact: the
    transport validated dtypes before this runs, so the astype is an
    identity guard, never a conversion."""
    pids = jnp.asarray(pids, jnp.int32)
    out = dict(cache)
    for name, a in pages.items():
        out[name] = cache[name].at[:, pids].set(
            jnp.moveaxis(a, 0, 1).astype(cache[name].dtype))
    return out


def copy_page(cache: dict, src, dst) -> dict:
    """Byte-exact pool-page copy across every layer and every storage
    leaf (K, V, scales) — the device half of copy-on-write. ``src``/
    ``dst`` may be traced scalars: one compiled executable serves every
    copy."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    out = dict(cache)
    for name, a in cache.items():
        if name in META_LEAVES:
            continue
        page = lax.dynamic_slice_in_dim(a, src, 1, axis=1)
        out[name] = lax.dynamic_update_slice_in_dim(a, page, dst, axis=1)
    return out


def set_length(cache: dict, slot, length) -> dict:
    """Set one slot's length pointer (admission of a shared prefix: the
    slot's visible history becomes the cached pages, no prefill ran)."""
    return {**cache, "lengths": cache["lengths"].at[slot].set(
        jnp.asarray(length, jnp.int32))}


def slot_rows(cache: dict, tables: np.ndarray, slot: int, n: int,
              name: str = "k") -> np.ndarray:
    """Test/debug helper: read back slot ``slot``'s first ``n`` logical
    rows of storage leaf ``name`` as [L, n, ...] host arrays, resolving
    the page indirection through the HOST table copy."""
    pool = np.asarray(cache[name])
    plen = pool.shape[2]
    out = []
    for r in range(n):
        pid = int(tables[slot, r // plen])
        out.append(pool[:, pid, r % plen])
    return np.stack(out, axis=1)


# --------------------------------------------------------------------------- #
# host-side allocator
# --------------------------------------------------------------------------- #


class PagePool:
    """Free list + refcounts over ``num_pages`` pool pages. Page 0 is
    reserved (NULL) and never allocated. A page's refcount counts its
    holders — slots whose tables point at it plus the radix cache —
    and the page returns to the free list exactly when the count hits 0.
    Deterministic FIFO allocation order (tests replay it)."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        self.num_pages = int(num_pages)
        self.refs = np.zeros(self.num_pages, np.int32)
        self.refs[NULL_PAGE] = 1  # permanently held, never freed
        self._free: deque = deque(range(1, self.num_pages))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def usable_pages(self) -> int:
        """Pages that can ever hold data (everything but NULL)."""
        return self.num_pages - 1

    @property
    def live_count(self) -> int:
        return self.usable_pages - self.free_count

    @property
    def shared_count(self) -> int:
        """Pages with more than one holder (prefix sharing in effect)."""
        return int(np.sum(self.refs[1:] > 1))

    def alloc(self):
        """Pop a free page at refcount 1, or None when the pool is dry
        (the caller evicts or sheds — alloc itself never raises)."""
        if not self._free:
            return None
        pid = self._free.popleft()
        assert self.refs[pid] == 0
        self.refs[pid] = 1
        return pid

    def ref(self, pid: int) -> None:
        """Add a holder. Refusing to resurrect a freed page (refcount 0)
        is what makes use-after-free a loud error instead of corruption."""
        if pid == NULL_PAGE:
            raise ValueError("cannot take a reference on the NULL page")
        if self.refs[pid] <= 0:
            raise ValueError(f"page {pid} is free; ref would resurrect it")
        self.refs[pid] += 1

    def unref(self, pid: int) -> bool:
        """Drop a holder; returns True when this freed the page. A drop
        below zero is a double free — raised, never masked."""
        if pid == NULL_PAGE:
            raise ValueError("cannot drop a reference on the NULL page")
        if self.refs[pid] <= 0:
            raise ValueError(f"double free of page {pid}")
        self.refs[pid] -= 1
        if self.refs[pid] == 0:
            self._free.append(pid)
            return True
        return False


class _Node:
    """One radix-cache node: a pool page holding the K/V rows of
    ``tokens`` (a full ``page_len`` chunk for interior nodes, shorter for
    partial leaves at prompt tails)."""

    __slots__ = ("tokens", "page_id", "parent", "children", "last_use")

    def __init__(self, tokens: tuple, page_id: int, parent):
        self.tokens = tokens
        self.page_id = page_id
        self.parent = parent
        self.children: dict = {}
        self.last_use = 0


class RadixCache:
    """Prefix trie over page-sized token chunks -> pool pages.

    ``match`` walks full-page chunks by exact lookup, then closes with
    the best partial overlap among the children at the divergence point —
    the page backing that overlap is shared too, and the sharer's first
    write past the fork COWs it. ``insert`` registers a prefilled
    prompt's pages (the cache becomes a holder: refcount +1). Eviction is
    LRU over refcount-1 leaves (pages nobody but the cache holds);
    freeing a leaf can expose its parent as the next candidate.

    Every lookup/registration operation takes a ``salt`` (default ``""``)
    naming an isolation domain — multi-tenant serving salts with the
    tenant id so identical prompts under different tenants NEVER share
    pages (a cross-tenant prefix hit would leak one tenant's KV bytes
    into another's decode). Each salt owns its own trie root; eviction
    and accounting span all of them, so an idle tenant's cached prefixes
    still yield to a busy one under pressure."""

    def __init__(self, page_len: int, pool: PagePool):
        self.page_len = int(page_len)
        self.pool = pool
        self.root = _Node((), -1, None)  # the default ("") salt's root
        self._roots = {"": self.root}
        self._clock = 0
        self.evictions = 0

    def _root_for(self, salt: str) -> _Node:
        root = self._roots.get(salt)
        if root is None:
            root = _Node((), -1, None)
            self._roots[salt] = root
        return root

    def _touch(self, node: _Node) -> None:
        self._clock += 1
        node.last_use = self._clock

    @staticmethod
    def _overlap(a, b) -> int:
        n = 0
        for x, y in zip(a, b):
            if x != y:
                break
            n += 1
        return n

    def match(self, ids, salt: str = "") -> tuple:
        """Longest cached prefix of ``ids`` within ``salt``'s domain:
        returns (pages, matched) where ``pages`` back positions
        ``[0, matched)`` in order (the last may be partial: ``matched``
        can end mid-page)."""
        node, pages, matched = self._root_for(salt), [], 0
        rest = list(ids)
        while True:
            chunk = tuple(rest[: self.page_len])
            child = (node.children.get(chunk)
                     if len(chunk) == self.page_len else None)
            if child is not None and len(child.tokens) == self.page_len:
                pages.append(child.page_id)
                matched += self.page_len
                rest = rest[self.page_len:]
                self._touch(child)
                node = child
                continue
            best, bj = None, 0
            for c in node.children.values():
                j = self._overlap(c.tokens, rest)
                if j > bj:
                    best, bj = c, j
            if best is not None:
                pages.append(best.page_id)
                matched += bj
                self._touch(best)
            return pages, matched

    def insert(self, ids, page_at, salt: str = "") -> int:
        """Register a prefilled prompt's pages under ``salt``'s domain:
        ``page_at(i)`` resolves the prompt's logical page ``i`` (the
        slot's table). Existing nodes are touched, new ones take a cache
        reference on the slot's page. The partial tail (a prompt ending
        mid-page) becomes a partial leaf unless an existing child already
        covers it. Returns the number of nodes created."""
        node, created = self._root_for(salt), 0
        n = len(ids)
        full = n // self.page_len
        for i in range(full):
            chunk = tuple(ids[i * self.page_len:(i + 1) * self.page_len])
            child = node.children.get(chunk)
            if child is None:
                pid = page_at(i)
                if pid == NULL_PAGE or self.pool.refs[pid] != 1:
                    # not exclusively the slot's (window edge oddities);
                    # stop registering rather than freeze a moving page
                    return created
                child = _Node(chunk, pid, node)
                node.children[chunk] = child
                self.pool.ref(pid)
                created += 1
            self._touch(child)
            node = child
        tail = tuple(ids[full * self.page_len:])
        if tail:
            for c in node.children.values():
                if self._overlap(c.tokens, tail) == len(tail):
                    return created  # an existing child already covers it
            pid = page_at(full)
            if pid != NULL_PAGE and self.pool.refs[pid] == 1:
                leaf = _Node(tail, pid, node)
                node.children[tail] = leaf
                self.pool.ref(pid)
                self._touch(leaf)
                created += 1
        return created

    def plan_adopt(self, ids, salt: str = "") -> list:
        """Chunk indices of ``ids`` with no existing trie node in
        ``salt``'s domain — the pages a cross-replica import must supply
        (non-destructive dry run of ``adopt``). Once one chunk is
        missing, every deeper chunk needs a node too (its parent path
        would be new), so the plan is always a suffix of the chunk
        list."""
        node = self._root_for(salt)
        n = len(ids)
        full = n // self.page_len
        tail = n % self.page_len
        total = full + (1 if tail else 0)
        for i in range(full):
            chunk = tuple(ids[i * self.page_len:(i + 1) * self.page_len])
            child = node.children.get(chunk)
            if child is None:
                return list(range(i, total))
            node = child
        if tail:
            t = tuple(ids[full * self.page_len:])
            if not any(self._overlap(c.tokens, t) == len(t)
                       for c in node.children.values()):
                return [full]
        return []

    def adopt(self, ids, page_for: dict, salt: str = "") -> tuple:
        """Graft imported pages into ``salt``'s trie: ``page_for[i]`` backs
        chunk ``i`` of ``ids`` (the last may be partial). New nodes take a
        cache reference on their page (the importer's own alloc reference
        is dropped by the caller afterwards, leaving exactly the cache as
        holder — the same end state as a slot's ``register_prompt``).
        Chunks that already have a node are touched and their imported
        page (if any was supplied) is returned in ``dups`` for the caller
        to free — idempotent under the dispatch-retry discipline. Returns
        (created, duplicate_page_ids)."""
        node, created, dups = self._root_for(salt), 0, []
        n = len(ids)
        full = n // self.page_len
        for i in range(full):
            chunk = tuple(ids[i * self.page_len:(i + 1) * self.page_len])
            child = node.children.get(chunk)
            if child is not None:
                if i in page_for:
                    dups.append(page_for[i])
                self._touch(child)
                node = child
                continue
            if i not in page_for:
                # a gap the import cannot fill (the plan predates a
                # concurrent eviction): stop grafting, free nothing here
                return created, dups
            child = _Node(chunk, page_for[i], node)
            node.children[chunk] = child
            self.pool.ref(page_for[i])
            self._touch(child)
            node = child
            created += 1
        tail = tuple(ids[full * self.page_len:])
        if tail and full in page_for:
            if any(self._overlap(c.tokens, tail) == len(tail)
                   for c in node.children.values()):
                dups.append(page_for[full])
            else:
                leaf = _Node(tail, page_for[full], node)
                node.children[tail] = leaf
                self.pool.ref(page_for[full])
                self._touch(leaf)
                created += 1
        return created, dups

    def _leaves(self):
        for root in self._roots.values():
            stack = list(root.children.values())
            while stack:
                n = stack.pop()
                if not n.children:
                    yield n
                stack.extend(n.children.values())

    def cached_prefixes(self, limit: int = 4) -> list:
        """The hottest cached token prefixes across every isolation
        domain: ``[(salt, ids)]`` for the ``limit`` most recently used
        leaves, hottest first. A leaf's root path IS a maximal cached
        prefix (interior nodes are covered by their descendants), so
        these are exactly what a drain-time cache handoff
        (tools/fleet.py) should export through the page transport."""
        scored = []
        for salt, root in self._roots.items():
            stack = list(root.children.values())
            while stack:
                n = stack.pop()
                if n.children:
                    stack.extend(n.children.values())
                    continue
                ids: list = []
                node = n
                while node is not None and node.parent is not None:
                    ids[:0] = node.tokens
                    node = node.parent
                scored.append((n.last_use, salt, ids))
        scored.sort(key=lambda t: t[0], reverse=True)
        return [(salt, ids) for _, salt, ids in scored[:max(0, limit)]]

    def evictable_count(self) -> int:
        """Pages eviction could free, cascading: nodes whose ENTIRE
        subtree is held only by the cache (freeing a leaf exposes its
        parent, so a refcount-1 chain frees end to end). Counting the
        cascade — not just today's leaves — is what keeps admission from
        deadlocking behind a deep cached prefix when no slot holds it."""

        def count(n: _Node) -> tuple:
            total, free = 0, True
            for c in n.children.values():
                ct, cf = count(c)
                total += ct
                free = free and cf
            if not free or self.pool.refs[n.page_id] != 1:
                return total, False
            return total + 1, True

        return sum(count(c)[0] for root in self._roots.values()
                   for c in root.children.values())

    def evict(self, n: int) -> int:
        """Free up to ``n`` pages, each time the least-recently-used
        refcount-1 leaf's; a freed leaf exposes its parent as a candidate.
        ONE walk of the trie however many pages go (a store that is full
        frees a prompt's worth of pages at every admission). Returns the
        pages freed: fewer than ``n`` when nothing more is evictable
        (every cached page left is also held by a live slot)."""
        heap = [(leaf.last_use, id(leaf), leaf) for leaf in self._leaves()
                if self.pool.refs[leaf.page_id] == 1]
        heapq.heapify(heap)
        freed = 0
        while heap and freed < n:
            _, _, node = heapq.heappop(heap)
            parent = node.parent
            self.pool.unref(node.page_id)
            del parent.children[node.tokens]
            self.evictions += 1
            freed += 1
            if (parent.parent is not None and not parent.children
                    and self.pool.refs[parent.page_id] == 1):
                heapq.heappush(heap, (parent.last_use, id(parent), parent))
        return freed

    def evict_one(self) -> bool:
        """Free the least-recently-used refcount-1 leaf's page. Returns
        False when nothing is evictable."""
        return self.evict(1) == 1

    def clear(self) -> None:
        """Drop every cache reference, across all salts (pool reset
        path)."""
        stack = [n for root in self._roots.values()
                 for n in root.children.values()]
        while stack:
            n = stack.pop()
            self.pool.unref(n.page_id)
            stack.extend(n.children.values())
        for root in self._roots.values():
            root.children = {}


class PageStore:
    """A page pool and the radix trie over it, with the calls that pin
    cached pages and land pages in the trie from outside a slot: what the
    paged layout's manager (``PagedKV``) and the contiguous layout's prefix
    store (``PrefixStore``) share."""

    def __init__(self, page_len: int, num_pages: int,
                 prefix_cache: bool = True):
        self.page_len = int(page_len)
        self.num_pages = int(num_pages)
        self.prefix_cache = bool(prefix_cache)

    def reset(self) -> None:
        """Fresh pool and trie: every retained byte is forgotten."""
        self.pool = PagePool(self.num_pages)
        self.radix = RadixCache(self.page_len, self.pool)

    def pages_for(self, tokens: int) -> int:
        """Worst-case pages ``tokens`` rows can occupy."""
        return -(-max(int(tokens), 0) // self.page_len)

    def _alloc(self) -> int:
        pid = self.pool.alloc()
        while pid is None:
            if not self.radix.evict_one():
                raise PagePoolExhausted(
                    f"page pool exhausted ({self.pool.usable_pages} pages, "
                    f"none free or evictable)")
            pid = self.pool.alloc()
        return pid

    def acquire_prefix(self, ids, salt: str = "") -> tuple:
        """Pin: radix-match ``ids`` (within ``salt``'s domain) and
        take a TRANSIENT reference on every matched page so eviction (and
        any COW planning) cannot touch them while the holder works (the
        transport serializes their bytes; the prefix store allocates
        beside them). Returns (page_ids, matched_tokens); the
        caller MUST ``release_pages`` the returned pages when done — the
        pin is a holder like any other."""
        if not self.prefix_cache:
            return [], 0
        pages, matched = self.radix.match(ids, salt=salt)
        npages = self.pages_for(matched)
        held = []
        for i in range(npages):
            self.pool.ref(pages[i])
            held.append(int(pages[i]))
        return held, matched

    def release_pages(self, pids) -> None:
        """Drop the transient references ``acquire_prefix`` (or a failed
        import) holds. Double drops raise — the pool's own discipline."""
        for pid in pids:
            self.pool.unref(int(pid))

    def alloc_import(self, n: int) -> list:
        """Allocate ``n`` pages for a transport import or a retention
        (refcount 1 held by the importer), the least recently used
        evictable pages freed first where the free list is short (one walk
        of the trie, ``RadixCache.evict``). All-or-nothing: on exhaustion
        every page of this batch is released before the raise, so a failed
        import can never leak pool capacity."""
        if n > self.pool.free_count:
            self.radix.evict(n - self.pool.free_count)
        pids = []
        try:
            for _ in range(n):
                pids.append(self._alloc())
        except PagePoolExhausted:
            self.release_pages(pids)
            raise
        return pids

    def finish_import(self, ids, chunk_pids: dict, salt: str = "") -> int:
        """Graft written import pages into ``salt``'s radix domain and
        drop the importer's references: created nodes end held by the
        cache alone (refcount 1, evictable — exactly a registered
        prompt's state); duplicate chunks' pages free immediately.
        Returns nodes created."""
        created, _ = self.radix.adopt(ids, chunk_pids, salt=salt)
        self.release_pages(chunk_pids.values())
        return created


class PrefixStore(PageStore):
    """Host half of the CONTIGUOUS layout's prefix store: the whole pages
    of finished prompts, kept beside the slots' strips under their token
    paths, so that a prompt whose leading pages were prefilled before has
    them copied into its strip and not computed again. The device half is a
    pool of ``num_pages`` pages laid out as the strips' rows are
    (``kv_cache.init_store``) and the two copy programs
    (``kv_cache.retain_rows`` strip -> pool, ``kv_cache.seat_rows`` pool ->
    strip), which take a slot's ``max_pages`` page ids in one fixed-shape
    row, NULL where nothing moves.

    A slot never points at a page here: a hit copies, so outside
    ``plan_retain`` .. ``commit`` every retained page is held by the trie
    alone and goes, least recently used leaf first, when the pool runs
    dry. Whole pages only: a prompt's tail past its last page boundary is
    prefilled again (under a page of tokens). The same correctness contract
    as the paged layout's sharing (this module's docstring): rows at
    position ``p`` depend on tokens ``0..p`` alone."""

    def __init__(self, page_len: int, max_pages: int, num_pages: int):
        super().__init__(page_len, num_pages)
        self.max_pages = int(max_pages)
        self.reset()

    def reset(self) -> None:
        super().reset()
        self.prefix_queries = 0
        self.prefix_hits = 0
        self.prompt_tokens = 0
        self.cached_tokens = 0
        self.pages_retained = 0

    def _row(self, page_at: dict) -> np.ndarray:
        """The copy programs' operand: page ``page_at[i]`` at the slot's
        logical page ``i``, the NULL page elsewhere."""
        row = np.full(self.max_pages, NULL_PAGE, np.int32)
        for i, pid in page_at.items():
            row[i] = pid
        return row

    def _whole(self, ids) -> list:
        """``ids`` cut to its whole pages (inside the slot's window)."""
        n = min(len(ids) // self.page_len, self.max_pages)
        return list(ids[: n * self.page_len])

    def lookup(self, ids, salt: str = "", worth=None) -> tuple:
        """The hit: (page-id row, cached) for the longest retained prefix
        of ``ids`` in whole pages within ``salt``'s domain, capped so that
        at least one prompt token is left to prefill (its logits seed the
        first sampled token). ``worth(cached) -> bool`` is the caller's
        say on whether copying that prefix in beats prefilling it (the
        engine's: fewer rows for the prefill programs to run); a prefix not
        worth it counts as no hit. The matched path is touched (LRU) either
        way."""
        self.prefix_queries += 1
        self.prompt_tokens += len(ids)
        pages, matched = self.radix.match(ids, salt=salt)
        n = min(min(matched, len(ids) - 1) // self.page_len, self.max_pages)
        if n and worth is not None and not worth(n * self.page_len):
            n = 0
        if n:
            self.prefix_hits += 1
            self.cached_tokens += n * self.page_len
        return self._row(dict(enumerate(pages[:n]))), n * self.page_len

    def plan_retain(self, ids, salt: str = ""):
        """Retention, first half: pages for the whole pages of the prompt
        ``ids`` that the trie does not hold yet, as (page-id row for the
        copy strip -> pool, ``{chunk: page}`` for ``commit``); None when
        there is nothing to retain or no page to be had. The pages the
        trie already holds of this prompt are pinned while the least
        recently used leaves make room, so eviction never cuts into the
        path being extended; where free and evictable pages together fall
        short, the LEADING missing pages are kept (a prefix is what a later
        prompt can use) and the rest is not retained: the request never
        fails for it."""
        whole = self._whole(ids)
        missing = self.radix.plan_adopt(whole, salt=salt)
        if not missing:
            return None
        held, _ = self.acquire_prefix(whole, salt=salt)
        try:
            if len(missing) > self.pool.free_count:
                self.radix.evict(len(missing) - self.pool.free_count)
            take = missing[:self.pool.free_count]
            fresh = self.alloc_import(len(take))
        finally:
            self.release_pages(held)
        if not take:
            return None
        chunk_pids = dict(zip(take, fresh))
        return self._row(chunk_pids), chunk_pids

    def commit(self, ids, chunk_pids: dict, salt: str = "") -> int:
        """Retention, second half (the copy is enqueued): the trie takes
        the pages under ``salt``. Returns the pages it took."""
        created = self.finish_import(self._whole(ids), chunk_pids, salt=salt)
        self.pages_retained += created
        return created

    def stats(self) -> dict:
        """Occupancy and effectiveness (merged into ``batcher.stats()`` ->
        ``/statz``; the hit keys are the paged layout's)."""
        return {
            "prefix_store_pages_total": self.pool.usable_pages,
            "prefix_store_pages_live": self.pool.live_count,
            "prefix_store_pages_retained": self.pages_retained,
            "prefix_queries": self.prefix_queries,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": (
                round(self.cached_tokens / self.prompt_tokens, 4)
                if self.prompt_tokens else None),
            "prefix_cached_tokens": self.cached_tokens,
            "radix_evictions": self.radix.evictions,
        }


class PagedKV(PageStore):
    """Host-side page manager for one engine: per-slot block tables +
    lengths, the pool, the radix cache, and admission pricing.

    The engine consults it before every dispatch (``ensure_writable`` —
    allocate growth pages, COW shared ones), mirrors device length
    advancement after (``advance``/``set_len``), and frees on slot
    release. The batcher prices admission in pages against
    ``can_admit`` so decode-time allocation is never the thing that
    discovers overload. ``tables`` is the numpy master the engine ships
    to the device before each dispatch."""

    def __init__(self, slots: int, page_len: int, max_pages: int,
                 num_pages: int, prefix_cache: bool = True):
        super().__init__(page_len, num_pages, prefix_cache)
        self.slots = int(slots)
        self.max_pages = int(max_pages)
        self.reset()

    def reset(self) -> None:
        """Fresh pool/trie/tables — pairs with a fresh zeroed device
        cache (engine.init_cache), including the batcher's cache-lost
        rebuild."""
        super().reset()
        self.tables = np.full((self.slots, self.max_pages), NULL_PAGE,
                              np.int32)
        self.host_len = np.zeros(self.slots, np.int64)
        self.priced = np.zeros(self.slots, np.int64)
        # prefix-cache effectiveness counters (stats())
        self.prefix_queries = 0
        self.prefix_hits = 0
        self.prompt_tokens = 0
        self.cached_tokens = 0
        self.cow_copies = 0

    # ---- pricing / admission ---------------------------------------------

    @property
    def usable_pages(self) -> int:
        return self.pool.usable_pages

    def future_need(self) -> int:
        """Pages the live slots may still demand: each priced slot can
        grow (and COW) until every page of its worst-case commitment is
        exclusively its own, so only exclusively-held pages discharge the
        debt. Conservative by construction — shared full-prefix pages are
        never actually COW'd, but counting them keeps decode-time
        allocation from ever being the thing that discovers overload."""
        need = 0
        for s in range(self.slots):
            if self.priced[s] <= 0:
                continue
            exclusive = sum(1 for pid in self.tables[s]
                            if pid != NULL_PAGE and self.pool.refs[pid] == 1)
            need += max(0, int(self.priced[s]) - exclusive)
        return need

    def available_pages(self) -> int:
        """Pages an incoming request could claim right now: free +
        immediately evictable, minus what live slots are still owed."""
        return (self.pool.free_count + self.radix.evictable_count()
                - self.future_need())

    def can_admit(self, need: int, slot: int = None) -> bool:
        """Whether ``need`` pages are claimable right now. ``slot`` is
        accepted (and ignored) for signature parity with the dp-sharded
        manager, where admission capacity is per-shard."""
        return need <= self.available_pages()

    # ---- slot lifecycle ---------------------------------------------------

    def match_prefix(self, slot: int, ids, cap_last: bool = True,
                     salt: str = "") -> int:
        """Admission half of prefix sharing: find the longest cached
        prefix of ``ids``, take references on its pages into ``slot``'s
        table, and return the cached length (capped at ``len(ids) - 1``
        so the last prompt token always runs through the model — its
        logits seed the first sampled token). ``cap_last=False`` lifts
        that cap for the disaggregated handoff seat: the prefill worker
        already sampled the first token, so the decode worker may share
        the FULL prompt and never dispatch a prefill at all.

        Idempotent under the batcher's dispatch retry: any holdings a
        FAILED earlier admission attempt left in this slot (shared refs,
        stranded COW copies) are released first — without that, a
        transient prefill fault would double-ref the cached pages, and
        pages nobody holds could never return to the free list."""
        for pi in range(self.max_pages):
            pid = int(self.tables[slot, pi])
            if pid != NULL_PAGE:
                self.pool.unref(pid)
        self.tables[slot] = NULL_PAGE
        self.host_len[slot] = 0
        self.prefix_queries += 1
        self.prompt_tokens += len(ids)
        if not self.prefix_cache:
            return 0
        pages, matched = self.radix.match(ids, salt=salt)
        cached = min(matched, len(ids) - (1 if cap_last else 0))
        npages = self.pages_for(cached)
        for i in range(npages):
            self.pool.ref(pages[i])
            self.tables[slot, i] = pages[i]
        self.host_len[slot] = cached
        if cached > 0:
            self.prefix_hits += 1
            self.cached_tokens += cached
        return cached

    def peek_prefix(self, ids, cap_last: bool = True,
                    salt: str = "") -> int:
        """Read-only admission probe: the cached-prefix length
        ``match_prefix`` would resolve for ``ids``, WITHOUT taking page
        references, touching slot state, or counting a query — the
        mixed-dispatch batcher's lane-eligibility check (a one-shot-
        sized miss takes the serial one-shot path; everything else
        rides the lane)."""
        if not self.prefix_cache:
            return 0
        _, matched = self.radix.match(ids, salt=salt)
        return min(matched, len(ids) - (1 if cap_last else 0))

    def ensure_writable(self, slot: int, from_pos: int, to_pos: int) -> list:
        """Make rows ``[from_pos, to_pos)`` of ``slot`` writable: allocate
        missing pages, and for shared pages (refcount > 1) allocate a
        fresh page, record a (src, dst) copy-on-write pair for the engine
        to execute on device, and swap the slot's reference. Idempotent —
        already-exclusive pages are untouched. Clamped to the paged
        window. Raises PagePoolExhausted when the pool is truly dry."""
        to_pos = min(int(to_pos), self.max_pages * self.page_len)
        from_pos = max(int(from_pos), 0)
        cows = []
        if to_pos <= from_pos:
            return cows
        first = from_pos // self.page_len
        last = -(-to_pos // self.page_len)  # exclusive
        for pi in range(first, last):
            pid = int(self.tables[slot, pi])
            if pid == NULL_PAGE:
                self.tables[slot, pi] = self._alloc()
            elif self.pool.refs[pid] > 1:
                fresh = self._alloc()
                cows.append((pid, fresh))
                self.tables[slot, pi] = fresh
                self.pool.unref(pid)
                self.cow_copies += 1
        return cows

    def register_prompt(self, slot: int, ids, salt: str = "") -> None:
        """Insert a freshly prefilled prompt's pages into ``salt``'s
        radix domain (post-prefill: the pages hold final bytes; the
        slot's decode writes land past the prompt and COW first)."""
        if self.prefix_cache:
            self.radix.insert(ids, lambda i: int(self.tables[slot, i]),
                              salt=salt)

    def quant_flags(self) -> np.ndarray:
        """Per-page ``hot_bf16`` policy flags for the device
        (``page_quant``): 1 = read this page as int8 (cold — exactly one
        holder), 0 = read at full precision (hot — radix-shared prefixes
        and fork pages, anything with more than one holder; also free
        pages, which nothing reads). Recomputed from live refcounts
        before every dispatch (engine._sync_tables), so a page flips
        hot<->cold as sharing changes — both representations are always
        written, so the flip is metadata-only."""
        return (self.pool.refs == 1).astype(np.int32)

    def advance(self, slot_counts: np.ndarray) -> None:
        """Mirror device length advancement after a dispatch (counts per
        slot, 0 for inactive)."""
        self.host_len += np.asarray(slot_counts, np.int64)

    def set_len(self, slot: int, n: int) -> None:
        self.host_len[slot] = int(n)

    def free_slot(self, slot: int) -> None:
        """Release every page reference the slot holds (pages shared
        with the radix cache or other slots survive; exclusive ones
        return to the free list) and clear its table row."""
        for pi in range(self.max_pages):
            pid = int(self.tables[slot, pi])
            if pid != NULL_PAGE:
                self.pool.unref(pid)
        self.tables[slot] = NULL_PAGE
        self.host_len[slot] = 0
        self.priced[slot] = 0

    # ---- observability ----------------------------------------------------

    def stats(self) -> dict:
        """Pool occupancy + prefix-cache effectiveness (merged into
        ``batcher.stats()`` -> ``/statz`` and the bench JSON)."""
        total = self.pool.usable_pages
        live = self.pool.live_count
        return {
            "kv_layout": "paged",
            "kv_page_len": self.page_len,
            "kv_pages_total": total,
            "kv_pages_free": self.pool.free_count,
            "kv_pages_live": live,
            "kv_pool_utilization": round(live / max(total, 1), 4),
            "kv_pages_shared": self.pool.shared_count,
            "prefix_queries": self.prefix_queries,
            "prefix_hits": self.prefix_hits,
            "prefix_hit_rate": (
                round(self.cached_tokens / self.prompt_tokens, 4)
                if self.prompt_tokens else None),
            "prefix_cached_tokens": self.cached_tokens,
            "cow_copies": self.cow_copies,
            "radix_evictions": self.radix.evictions,
            # hot_bf16 policy mix over LIVE pages (cold = read as int8):
            # with the two row byte widths, the bytes a cache walk reads
            "kv_pages_quant": int(np.sum(self.pool.refs[1:] == 1)),
        }


# --------------------------------------------------------------------------- #
# dp-sharded host allocator
# --------------------------------------------------------------------------- #


class _PoolAggregate:
    """Read-only pool view summed over a ShardedPagedKV's shard pools —
    the surface ``batcher.refresh_gauges`` / bench / tests consume.
    ``refs`` concatenates the shard pools' refcount arrays in shard
    order, so it is indexed by GLOBAL page id (a copy: mutate the shard
    pools, never this)."""

    def __init__(self, owner: "ShardedPagedKV"):
        self._owner = owner
        self.num_pages = owner.num_pages

    @property
    def usable_pages(self) -> int:
        return sum(sh.pool.usable_pages for sh in self._owner.shards)

    @property
    def free_count(self) -> int:
        return sum(sh.pool.free_count for sh in self._owner.shards)

    @property
    def live_count(self) -> int:
        return sum(sh.pool.live_count for sh in self._owner.shards)

    @property
    def shared_count(self) -> int:
        return sum(sh.pool.shared_count for sh in self._owner.shards)

    @property
    def refs(self) -> np.ndarray:
        return np.concatenate([sh.pool.refs for sh in self._owner.shards])


class _ShardedRadix:
    """The slim radix surface external callers touch (page_transport's
    ``plan_adopt``, serve's drain-time ``cached_prefixes``, tests'
    ``match``), dispatched over per-shard tries. An import is planned and
    landed on ONE shard — ``plan_adopt`` records the chosen shard so the
    owner's ``alloc_import``/``finish_import`` land the pages there —
    picked as the shard already caching the most of the prefix (fewest
    missing chunks), free pages breaking ties."""

    def __init__(self, owner: "ShardedPagedKV"):
        self._owner = owner

    @property
    def evictions(self) -> int:
        return sum(sh.radix.evictions for sh in self._owner.shards)

    def match(self, ids, salt: str = "") -> tuple:
        """Longest cached prefix across every shard's trie, page ids
        GLOBAL. Ties go to the lowest shard (deterministic)."""
        best_pages, best_matched = [], 0
        for s, sh in enumerate(self._owner.shards):
            pages, matched = sh.radix.match(ids, salt=salt)
            if matched > best_matched:
                base = s * self._owner.pages_per_shard
                best_pages = [p + base for p in pages]
                best_matched = matched
        return best_pages, best_matched

    def plan_adopt(self, ids, salt: str = "") -> list:
        o = self._owner
        best, best_key = 0, None
        for s, sh in enumerate(o.shards):
            missing = len(sh.radix.plan_adopt(ids, salt=salt))
            key = (missing, -sh.pool.free_count, s)
            if best_key is None or key < best_key:
                best, best_key = s, key
        o._import_shard = best
        return o.shards[best].radix.plan_adopt(ids, salt=salt)

    def cached_prefixes(self, limit: int = 4) -> list:
        """Hottest cached prefixes across shards (per-shard LRU clocks
        are independent; round-robin merge keeps every shard's hottest
        represented)."""
        per = [sh.radix.cached_prefixes(limit) for sh in self._owner.shards]
        out: list = []
        i = 0
        while len(out) < max(0, limit) and any(per):
            for entries in per:
                if i < len(entries) and len(out) < limit:
                    out.append(entries[i])
            i += 1
            if all(i >= len(entries) for entries in per):
                break
        return out


class ShardedPagedKV:
    """Host-side page manager for a dp-sharded engine: ``dp_size``
    independent ``PagedKV`` allocators, one per dp shard, behind the
    global-slot / global-page-id surface the engine and batcher already
    speak.

    Layout contract (mirrors ``cache_pspecs(dp=...)``): global slot
    ``i`` lives on shard ``i // slots_per_shard``; shard ``s`` owns pool
    pages ``[s * pages_per_shard, (s+1) * pages_per_shard)`` and page
    ``s * pages_per_shard`` is that shard's NULL page (the reserved
    scribble target — so a slot's table NEVER references a page outside
    its own shard, and the jitted dispatch needs zero cross-shard
    traffic to resolve any table entry). ``tables`` materializes the
    global [slots, max_pages] int32 view with shard-local NULLs mapped
    to the owning shard's null page. ``host_len``/``priced`` are master
    numpy arrays whose per-shard slices are rewired INTO the shard
    allocators as views, so in-place writes on either side stay
    coherent.

    Prefix sharing is per shard (each shard's radix trie only ever
    references its own pages); cross-shard reuse happens by page
    MIGRATION (engine.migrate_slot / the batcher's rebalance planner),
    never by a table pointing across the dp axis."""

    def __init__(self, dp_size: int, slots: int, page_len: int,
                 max_pages: int, num_pages: int,
                 prefix_cache: bool = True):
        dp_size = int(dp_size)
        slots = int(slots)
        num_pages = int(num_pages)
        if dp_size < 1:
            raise ValueError("dp_size must be >= 1")
        if slots % dp_size:
            raise ValueError(
                f"slots ({slots}) must divide evenly over dp_size "
                f"({dp_size}) — each shard serves slots/dp slots")
        if num_pages % dp_size:
            raise ValueError(
                f"kv_num_pages ({num_pages}) must divide evenly over "
                f"dp_size ({dp_size}) — the pool page axis shards over "
                "'dp'")
        if num_pages // dp_size < 2:
            raise ValueError(
                "kv_num_pages must give every dp shard >= 2 pages "
                "(page 0 of each shard is its reserved NULL page)")
        self.dp_size = dp_size
        self.slots = slots
        self.slots_per_shard = slots // dp_size
        self.page_len = int(page_len)
        self.max_pages = int(max_pages)
        self.num_pages = num_pages
        self.pages_per_shard = num_pages // dp_size
        self.prefix_cache = bool(prefix_cache)
        self.shards = [
            PagedKV(self.slots_per_shard, self.page_len, self.max_pages,
                    self.pages_per_shard, prefix_cache=prefix_cache)
            for _ in range(dp_size)
        ]
        self.radix = _ShardedRadix(self)
        self.pool = _PoolAggregate(self)
        self._import_shard = None
        self.reset()

    # ---- shard/global coordinate helpers ----------------------------------

    def shard_of(self, slot: int) -> int:
        return int(slot) // self.slots_per_shard

    def local_slot(self, slot: int) -> int:
        return int(slot) % self.slots_per_shard

    def _shard_base(self, s: int) -> int:
        return s * self.pages_per_shard

    def reset(self) -> None:
        for sh in self.shards:
            sh.reset()
        # master slot-state arrays; shard allocators hold slice VIEWS so
        # their in-place writes (free_slot, match_prefix, set_len) land
        # in the master the engine/batcher read
        self.host_len = np.zeros(self.slots, np.int64)
        self.priced = np.zeros(self.slots, np.int64)
        spb = self.slots_per_shard
        for s, sh in enumerate(self.shards):
            sh.host_len = self.host_len[s * spb:(s + 1) * spb]
            sh.priced = self.priced[s * spb:(s + 1) * spb]
        self._import_shard = None

    # ---- global table view -------------------------------------------------

    @property
    def tables(self) -> np.ndarray:
        """Global [slots, max_pages] block tables with GLOBAL page ids:
        shard s's local entries offset by its page base, so its local
        NULL (0) becomes page ``s * pages_per_shard`` — exactly that
        shard's reserved null page under the dp-sharded pool layout.
        Recomputed per access (a copy: write through the shard
        allocators, never this view)."""
        return np.vstack([sh.tables + self._shard_base(s)
                          for s, sh in enumerate(self.shards)])

    # ---- pricing / admission ----------------------------------------------

    def pages_for(self, tokens: int) -> int:
        return self.shards[0].pages_for(tokens)

    @property
    def usable_pages(self) -> int:
        """Admission ceiling: the most pages ONE slot can ever hold. A
        slot's pages all live on its own shard, so this is a single
        shard's capacity — a request needing more can never fit, however
        empty the other shards are. (Aggregate capacity is
        ``pool.usable_pages``.)"""
        return self.pages_per_shard - 1

    def available_pages(self) -> int:
        return sum(sh.available_pages() for sh in self.shards)

    def can_admit(self, need: int, slot: int = None) -> bool:
        """Whether ``need`` pages are claimable — on ``slot``'s own shard
        when a slot is named (admission targets a specific seat), on ANY
        shard otherwise."""
        if slot is not None:
            return self.shards[self.shard_of(slot)].can_admit(need)
        return any(sh.can_admit(need) for sh in self.shards)

    # ---- slot lifecycle (global-slot delegation) --------------------------

    def match_prefix(self, slot: int, ids, cap_last: bool = True,
                     salt: str = "") -> int:
        return self.shards[self.shard_of(slot)].match_prefix(
            self.local_slot(slot), ids, cap_last=cap_last, salt=salt)

    def ensure_writable(self, slot: int, from_pos: int,
                        to_pos: int) -> list:
        s = self.shard_of(slot)
        base = self._shard_base(s)
        return [(src + base, dst + base) for src, dst in
                self.shards[s].ensure_writable(self.local_slot(slot),
                                               from_pos, to_pos)]

    def peek_prefix(self, ids, cap_last: bool = True, salt: str = "",
                    shard: int = 0) -> int:
        """Read-only probe against ONE shard's radix domain (prefix
        domains are per shard, so the caller names the shard the slot
        would seat on)."""
        return self.shards[shard].peek_prefix(ids, cap_last=cap_last,
                                              salt=salt)

    def register_prompt(self, slot: int, ids, salt: str = "") -> None:
        self.shards[self.shard_of(slot)].register_prompt(
            self.local_slot(slot), ids, salt=salt)

    def advance(self, slot_counts: np.ndarray) -> None:
        self.host_len += np.asarray(slot_counts, np.int64)

    def set_len(self, slot: int, n: int) -> None:
        self.host_len[slot] = int(n)

    def free_slot(self, slot: int) -> None:
        self.shards[self.shard_of(slot)].free_slot(self.local_slot(slot))

    def quant_flags(self) -> np.ndarray:
        """Global per-page flags, shard-major — the device
        ``page_quant``'s P('dp') layout."""
        return np.concatenate([sh.quant_flags() for sh in self.shards])

    # ---- page transport (global page ids) ---------------------------------

    def acquire_prefix(self, ids, salt: str = "") -> tuple:
        """Export pin against the shard caching the longest prefix of
        ``ids``; returns GLOBAL page ids."""
        if not self.prefix_cache:
            return [], 0
        best_s, best_matched = None, 0
        for s, sh in enumerate(self.shards):
            _, matched = sh.radix.match(ids, salt=salt)
            if matched > best_matched:
                best_s, best_matched = s, matched
        if best_s is None:
            # still counts as a query on shard 0 (the vanilla manager's
            # acquire path never touches counters; neither does this)
            return [], 0
        held, matched = self.shards[best_s].acquire_prefix(ids, salt=salt)
        base = self._shard_base(best_s)
        return [pid + base for pid in held], matched

    def release_pages(self, pids) -> None:
        pps = self.pages_per_shard
        for pid in pids:
            pid = int(pid)
            self.shards[pid // pps].pool.unref(pid % pps)

    def alloc_import(self, n: int) -> list:
        """Allocate ``n`` import pages on the shard ``radix.plan_adopt``
        chose (falling back to the freest shard when no plan ran);
        returns GLOBAL page ids. All-or-nothing like the vanilla path."""
        s = self._import_shard
        if s is None:
            s = max(range(self.dp_size),
                    key=lambda i: (self.shards[i].pool.free_count, -i))
            self._import_shard = s
        base = self._shard_base(s)
        return [pid + base for pid in self.shards[s].alloc_import(n)]

    def finish_import(self, ids, chunk_pids: dict, salt: str = "") -> int:
        """Graft import pages (GLOBAL ids, on the planned shard) into
        that shard's radix; clears the sticky import-shard choice."""
        s = self._import_shard
        if s is None and chunk_pids:
            s = next(iter(chunk_pids.values())) // self.pages_per_shard
        self._import_shard = None
        if s is None:
            return 0
        base = self._shard_base(s)
        local = {i: pid - base for i, pid in chunk_pids.items()}
        return self.shards[s].finish_import(ids, local, salt=salt)

    # ---- observability ----------------------------------------------------

    def shard_occupancy(self) -> list:
        """Occupied slots per shard (host_len > 0) — the rebalance
        planner's input and the ``picotron_shard_occupancy`` gauge."""
        spb = self.slots_per_shard
        return [int(np.count_nonzero(
            self.host_len[s * spb:(s + 1) * spb] > 0))
            for s in range(self.dp_size)]

    def stats(self) -> dict:
        total = self.pool.usable_pages
        live = self.pool.live_count
        agg = {
            "kv_layout": "paged",
            "kv_page_len": self.page_len,
            "kv_pages_total": total,
            "kv_pages_free": self.pool.free_count,
            "kv_pages_live": live,
            "kv_pool_utilization": round(live / max(total, 1), 4),
            "kv_pages_shared": self.pool.shared_count,
            "prefix_queries": sum(sh.prefix_queries for sh in self.shards),
            "prefix_hits": sum(sh.prefix_hits for sh in self.shards),
            "cow_copies": sum(sh.cow_copies for sh in self.shards),
            "radix_evictions": self.radix.evictions,
            "kv_pages_quant": sum(
                int(np.sum(sh.pool.refs[1:] == 1)) for sh in self.shards),
            "dp_size": self.dp_size,
            "kv_shard_pages_live": [sh.pool.live_count
                                    for sh in self.shards],
            "shard_occupancy": self.shard_occupancy(),
        }
        prompt = sum(sh.prompt_tokens for sh in self.shards)
        cached = sum(sh.cached_tokens for sh in self.shards)
        agg["prefix_hit_rate"] = (round(cached / prompt, 4)
                                  if prompt else None)
        agg["prefix_cached_tokens"] = cached
        return agg
