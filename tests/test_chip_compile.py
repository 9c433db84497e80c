"""Compile every main-path Pallas kernel for a described v5e at SmolLM-1.7B
widths (H 2048, 32/32 heads of 64, FFN 8192, vocab 49152, seq 2048, bf16),
and the serving programs themselves, to read off the compiled text that the
KV cache never leaves its buffer inside them.

No chip is needed and nothing runs: the TPU compiler installed with jax
compiles for a ``v5e:2x2`` that is described, not attached, and raises what
the chip's compiler would raise (a block shape the lowering refuses, a slice
Mosaic cannot align, too much VMEM). Interpret-mode parity lives in
tests/test_pallas_kernels.py and tests/test_decode_kernel.py; the compiled
kernels run against their oracles on the chip in ``chip_smoke.py``.

The topology is described inside a module-scoped fixture — never at import,
in a ``skipif`` or in a ``parametrize`` argument — so every xdist worker
collects the same tests and only the worker given this file loads the TPU
library. Keep these compiles in this ONE file.
"""

import math
import os
import re
from functools import partial

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from picotron_tpu.ops.pallas import quant_matmul as qm
from picotron_tpu.ops.pallas.decode_attention import (
    flash_decode_attention,
    flash_decode_stacked,
)
from picotron_tpu.ops.pallas.flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    _pick_block,
    _scale_folds,
    causal_kv_blocks,
    flash_attention,
    flash_attention_with_lse,
    flash_block_grads,
)
from picotron_tpu.ops.pallas.kda_step import kda_step_stacked
from picotron_tpu.ops.pallas.rmsnorm import rms_norm_pallas
from picotron_tpu.ops.pallas.ssm_step import ssm_step_stacked

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
HID, HEADS, D, FFN, VOCAB, SEQ = 2048, 32, 64, 8192, 49152, 2048
SCALE = D ** -0.5
# the serving geometry chip_smoke.py drives: 8 slots x 2048, 64-row pages
SLOTS, PAGE = 8, 64
MAXP = SEQ // PAGE
NPAGES = SLOTS * MAXP + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compile cache
    off around these compiles: an entry written for a described device
    cannot be read back without one and would warn on every later run."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sum32(x):
    return x.astype(F32).sum()


def _flash_fwd():
    q = ((4, SEQ, HEADS, D), BF16)
    return (lambda q, k, v: flash_attention(q, k, v, SCALE)), [q, q, q]


def _flash_bwd():
    q = ((4, SEQ, HEADS, D), BF16)
    return jax.grad(lambda q, k, v: _sum32(flash_attention(q, k, v, SCALE)),
                    argnums=(0, 1, 2)), [q, q, q]


# The two training cells' own attention calls (BENCHMARK.json): micro-batch 3
# of SmolLM at 2048, and one sequence of Mistral's 16 tp-local heads of 128
# at 4096; with what the kernels decide for each at trace time: the tile
# pairs a head's causal walk visits (every one masked) and whether the scale
# is folded into an operand.
CELL_SHAPES = {
    "train2k": ((3, 2048, 32, 64), dict(tiles=10, scale_folded=True)),
    "pp2tp2": ((1, 4096, 16, 128), dict(tiles=36, scale_folded=False)),
    # what train-2k's layer stack calls since PR 48: heads of 64 two to a
    # 128-lane block (Mosaic accepts the head-pair block), the same walk
    "train2k_paired": ((3, 2048, 32, 64), dict(tiles=10, scale_folded=True)),
}
CELL_LAYOUT = {"train2k_paired": "paired"}  # the others fold


def _flash_cell(cell, backward):
    shape, _ = CELL_SHAPES[cell]
    q = (shape, BF16)
    attend = lambda q, k, v: flash_attention(
        q, k, v, shape[-1] ** -0.5, layout=CELL_LAYOUT.get(cell, "folded"))
    if not backward:
        return attend, [q, q, q]
    return jax.grad(lambda q, k, v: _sum32(attend(q, k, v)),
                    argnums=(0, 1, 2)), [q, q, q]


def _flash_block_grads():
    # the ring-attention building block: one off-diagonal (full-attend)
    # block's gradients from a global out/lse, at cp=2's half sequence
    q = ((1, SEQ // 2, HEADS, D), BF16)
    lse = ((1, SEQ // 2, HEADS), F32)
    return (lambda q, k, v, o, l, do: flash_block_grads(
        q, k, v, o, l, do, SCALE, causal=False)), [q, q, q, q, lse, q]


def _flash_with_lse():
    q = ((1, SEQ // 2, HEADS, D), BF16)
    return (lambda q, k, v: flash_attention_with_lse(
        q, k, v, SCALE, causal=False)), [q, q, q]


def _rms_fwd():
    return rms_norm_pallas, [((4 * SEQ, HID), BF16), ((HID,), BF16)]


def _rms_fwd_rows(rows, hidden):
    """The serving head's norm over a decode step's rows, one a slot, at a
    width whose 512 KB block is not a power of two of rows (5,120: 51)."""
    return rms_norm_pallas, [((rows, hidden), BF16), ((hidden,), BF16)]


def _rms_bwd(rows=4 * SEQ, hidden=HID):
    return jax.grad(lambda x, w: _sum32(rms_norm_pallas(x, w)),
                    argnums=(0, 1)), [((rows, hidden), BF16),
                                      ((hidden,), BF16)]


def _decode(layout, b, s):
    """flash_decode_attention in one cache layout the engine can select,
    at one of its three call shapes (decode, verify window, prefill
    chunk)."""
    q = ((b, s, HEADS, D), BF16)
    lens = ((b,), I32)
    cache = lambda dt: ((b, SEQ, HEADS, D), dt)
    scales = ((b, SEQ, HEADS), F32)
    pool = lambda dt: ((NPAGES, PAGE, HEADS, D), dt)
    pscales = ((NPAGES, PAGE, HEADS), F32)
    tables = ((b, MAXP), I32)
    if layout == "contiguous":
        return (lambda q, k, v, n: flash_decode_attention(
            q, k, v, n, SCALE)), [q, cache(BF16), cache(BF16), lens]
    if layout == "int8":
        return (lambda q, k, v, ks, vs, n: flash_decode_attention(
            q, k, v, n, SCALE, k_scale=ks, v_scale=vs)), \
            [q, cache(I8), cache(I8), scales, scales, lens]
    if layout == "paged":
        return (lambda q, k, v, bt, n: flash_decode_attention(
            q, k, v, n, SCALE, block_tables=bt)), \
            [q, pool(BF16), pool(BF16), tables, lens]
    assert layout == "hot_bf16"
    return (lambda q, k, v, kq, vq, ks, vs, bt, bq, n: flash_decode_attention(
        q, k, v, n, SCALE, k_quant=kq, v_quant=vq, k_scale=ks, v_scale=vs,
        block_tables=bt, block_quant=bq)), \
        [q, pool(BF16), pool(BF16), pool(I8), pool(I8), pscales, pscales,
         tables, tables, lens]


# the carried K/V leaves of the two Llama serving cells, of the SDAR cell and
# of the Solar cell (BENCHMARK.json): (layers, slots, window, rows, lanes),
# query heads, head size
STACKED_LEAVES = {"smollm": ((24, 4, 2048, 16, 128), 32, 64),
                  "mistral": ((16, 8, 2048, 8, 128), 32, 128),
                  # the two cells with the most slots: SDAR's round (a block
                  # of 4 positions beside a kv head's 8 query heads: 128 query
                  # rows a slot) and Solar's two GQA layers
                  "sdar": ((12, 32, 12288, 4, 128), 128, 128),
                  "solar": ((2, 64, 8192, 8, 128), 64, 128),
                  # five query heads a K/V head: a score tile that is not a
                  # whole sublane group
                  "falcon": ((4, 64, 6144, 4, 128), 20, 128)}


def _decode_stacked(cell, early=None):
    """flash_decode_stacked, the ``S == 1`` step's kernel, on a cell's own
    stacked leaf with a traced layer index; with ``early`` the form of two
    limits a slot (twice the query heads, the first ``early[0]`` of each kv
    head's stopping ``early[1]`` keys early: the strided read of a pair of
    cache rows as 32-bit words has to lower)."""
    leaf, heads, d = STACKED_LEAVES[cell]
    heads, form = (heads, {}) if early is None else (2 * heads,
                                                     {"early": early})
    return (lambda q, k, v, n, layer: flash_decode_stacked(
        q, k, v, n, d ** -0.5, layer, **form)), \
        [((leaf[1], 1, heads, d), BF16), (leaf, BF16), (leaf, BF16),
         ((leaf[1],), I32), ((), I32)]


# the sliding layers' rings of the Trinity cell: 16 slots x (4096 + 512)
RING_LEAF, RING_WINDOW = (7, 16, 4608, 8, 128), 4096


def _decode_ring():
    """flash_decode_stacked in its ring form (``window=``: a third scalar
    operand, a second bound in the mask), on the Trinity cell's own ring
    leaf with a traced layer index."""
    return (lambda q, k, v, n, layer: flash_decode_stacked(
        q, k, v, n, 128 ** -0.5, layer, window=RING_WINDOW)), \
        [((RING_LEAF[1], 1, 48, 128), BF16), (RING_LEAF, BF16),
         (RING_LEAF, BF16), ((RING_LEAF[1],), I32), ((), I32)]


# the MiMo cell's four cache leaves (32 slots x 16,384; rings of 128 + 512),
# a row's heads merged: (K leaf, V leaf, window), 64 query heads of 192
MIMO_LEAVES = {
    "full": ((3, 32, 16384, 768), (3, 32, 16384, 512), 0),
    "ring": ((10, 32, 640, 1536), (10, 32, 640, 1024), 128),
}


def _decode_mimo(kind, sink=None):
    """``mimo_v2``'s decode attend as the block calls it: K rows wider than
    V rows, and for the rings a window and a sink a query head (``sink``
    given: with or without it whatever the kind, as ``decode_attend`` hands
    a full layer's on)."""
    from picotron_tpu.ops.pallas import decode_attention as da

    k_leaf, v_leaf, window = MIMO_LEAVES[kind]
    slots, with_sink = k_leaf[1], bool(window) if sink is None else sink

    def attend(q, k, v, pos, layer, sink):
        # blocks of 512 tokens, the largest divisor under 1 MiB of K, and
        # of a ring 128: the largest that is no more than the window
        T, row_bytes = k.shape[2], 2 * k.shape[3]
        assert {"full": da._stacked_block_rows(T, row_bytes) == 512,
                "ring": da._ring_block_rows(T, row_bytes, 128) == 128}[kind]
        return flash_decode_stacked(
            q, k[:, :, :, None], v[:, :, :, None], pos + 1, 192 ** -0.5,
            layer, window=window or None, sink=sink if with_sink else None)

    return attend, [((slots, 1, 64, 192), BF16), (k_leaf, BF16),
                    (v_leaf, BF16), ((slots,), I32), ((), I32),
                    ((64,), F32)]


def _quant(m, k, n):
    return (lambda x, q, s: qm.quant_matmul_pallas(x, q, s)), \
        [((m, k), BF16), ((k, n), I8), ((n,), F32)]


# the three recurrent cells' stacked float32 state leaves and one slot's B/C
# rows: Nemotron's eight groups of sixteen heads, Granite's one row for all
# heads, SALA's row a head (k and q), Falcon-H1's two groups of sixteen heads
# of 128 x 256 (four times the others' state a head)
SSM_LEAVES = {"nemotron": ((5, 128, 128, 64, 128), (8, 128)),
              "falcon": ((4, 64, 32, 128, 256), (2, 256)),
              "granite": ((9, 64, 128, 64, 128), (128,)),
              "sala": ((9, 8, 32, 128, 128), (32, 128))}


def _ssm_step(cell):
    """ssm_step_stacked, the recurrent layers' decode step, on a cell's own
    stacked state leaf with a traced row."""
    leaf, bc = SSM_LEAVES[cell]
    _, slots, heads, hd, _ = leaf
    return ssm_step_stacked, \
        [((slots, 1, heads, hd), BF16), ((slots, 1, heads), F32),
         ((heads,), F32), ((slots, 1) + bc, BF16), ((slots, 1) + bc, BF16),
         (leaf, F32), ((), I32)]


def _kda_step():
    """kda_step_stacked, the delta rule's decode step, on the Solar cell's
    own stacked state leaf (6 KDA layers x 64 slots x 64 heads of 128 x
    128) with a traced row."""
    leaf = (6, 64, 64, 128, 128)
    _, slots, heads, keys, values = leaf
    qk = ((slots, 1, heads, keys), BF16)
    return kda_step_stacked, \
        [qk, qk, ((slots, 1, heads, values), BF16),
         ((slots, 1, heads, keys), F32), ((slots, 1, heads), F32),
         (leaf, F32), ((), I32)]


DECODE_SHAPES = {"decode": (SLOTS, 1), "verify": (SLOTS, 5),
                 "chunk": (1, 256)}
CASES = {
    "flash_fwd": _flash_fwd,
    "flash_bwd": _flash_bwd,
    "flash_block_grads": _flash_block_grads,
    "flash_with_lse": _flash_with_lse,
    **{f"flash_{'bwd' if bwd else 'fwd'}_{cell}":
       (lambda cell=cell, bwd=bwd: _flash_cell(cell, bwd))
       for cell in CELL_SHAPES for bwd in (False, True)},
    "rmsnorm_fwd": _rms_fwd,
    "rmsnorm_bwd": _rms_bwd,
    "rmsnorm_fwd_falcon_decode": lambda: _rms_fwd_rows(64, 5120),
    # rows no block of whole sublane groups divides: 20 under a block of 16
    # (one block would be the whole array, half of one is 4 rows), 100 under
    # 32; the rows are made up to whole blocks
    "rmsnorm_fwd_rows_20": lambda: _rms_fwd_rows(20, 16384),
    "rmsnorm_fwd_rows_100": lambda: _rms_fwd_rows(100, 5120),
    "rmsnorm_bwd_rows_100": lambda: _rms_bwd(100, 5120),
    **{f"decode_{layout}_{name}":
       (lambda layout=layout, b=b, s=s: _decode(layout, b, s))
       for layout in ("contiguous", "int8", "paged", "hot_bf16")
       for name, (b, s) in DECODE_SHAPES.items()},
    **{f"decode_stacked_{cell}": (lambda cell=cell: _decode_stacked(cell))
       for cell in STACKED_LEAVES},
    # SDAR's fused forward: two blocks of 4 rows beside a kv head's 8 heads
    "decode_stacked_sdar_two_blocks": lambda: _decode_stacked("sdar", (32, 4)),
    "decode_ring_trinity": _decode_ring,
    "decode_full_mimo": lambda: _decode_mimo("full"),
    "decode_full_sink_mimo": lambda: _decode_mimo("full", sink=True),
    "decode_ring_mimo": lambda: _decode_mimo("ring"),
    "quant_matmul_up": lambda: _quant(8, HID, FFN),
    "quant_matmul_down": lambda: _quant(8, FFN, HID),
    "quant_matmul_head": lambda: _quant(8, HID, VOCAB),
    **{f"ssm_step_{cell}": (lambda cell=cell: _ssm_step(cell))
       for cell in SSM_LEAVES},
    "kda_step_solar": _kda_step,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{case}: the compiled program holds no Pallas kernel"


@pytest.mark.parametrize("cell", sorted(CELL_SHAPES))
def test_flash_walk_at_cell_shapes(cell, one_chip):
    """What the compiled training kernels walk at each cell's shape, from
    the kernels' own loop bound and fold test: the tile pairs a head visits,
    whether the scale is folded into an operand; and the backward is one
    kernel a call."""
    (_, seq, _, d), want = CELL_SHAPES[cell]
    bq = _pick_block(seq, DEFAULT_BLOCK_Q)
    bk = _pick_block(seq, DEFAULT_BLOCK_K)
    walked = sum(int(causal_kv_blocks(seq // bk, (qi + 1) * bq - 1, bk))
                 for qi in range(seq // bq))
    assert dict(tiles=walked, scale_folded=_scale_folds(d ** -0.5)) == want
    fn, shapes = _flash_cell(cell, backward=True)
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    # the forward (for its residuals) and one backward kernel
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2, text


# --------------------------------------------------------------------------- #
# the serving programs: the KV cache never leaves its buffer
# --------------------------------------------------------------------------- #
#
# Compiled for the chip, a cache leaf fed through a ``lax.scan`` as xs/ys
# becomes, per layer, a fusion that slices the layer out into a buffer of
# its own and one that writes the whole layer back, and per decode step a
# copy of the whole stacked array into the step loop's carry (PERF.md,
# PR 26: 56 % of SmolLM's decode step). The engine keeps the leaves in the
# scan's carry and indexes them instead; these tests read the compiled
# text and the compiler's own byte counts to hold it to that.

SERVE_LAYERS, SERVE_SLOTS = 2, 4
_SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")
_CALLED = re.compile(r"(?:body|condition|to_apply|calls)=%([\w.\-]+)")


def _kv_elems(shape: str, tail) -> int:
    """Elements of the largest K/V-shaped array (dims end in ``tail``, the
    two minor dimensions of the cache's K leaf: rows of heads, lanes of a
    row) in an HLO shape string; a tuple has several."""
    best = 0
    for dims in _SHAPE.findall(shape):
        dims = tuple(int(d) for d in dims.split(",") if d)
        if dims[-len(tail):] == tail:
            best = max(best, math.prod(dims))
    return best


def _computations(text: str) -> dict:
    """HLO text -> {computation: [(name, shape, op, rest of the line)]}."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = _COMP.match(line)
        if head:
            cur = comps[head.group(1)] = []
        elif cur is not None and _INSTR.match(line):
            cur.append(_INSTR.match(line).groups())
    return comps


def _cache_movers(text: str, kv_shape: tuple) -> list:
    """Instructions that run inside a loop of the program and move a
    layer's elements of K or V (a leaf of ``kv_shape``) or more: a ``copy``, a (fused)
    ``dynamic-slice`` that lands them in a buffer of their own, a (fused)
    ``dynamic-update-slice`` whose update they are. A fusion that READS a
    layer through a dynamic-slice and reduces it (the attention
    contractions), or scatters a few rows into the stacked array in place,
    is what the program should be made of and is not listed."""
    comps = _computations(text)
    shapes = {c: {n: sh for n, sh, _, _ in ins} for c, ins in comps.items()}
    layer_elems = math.prod(kv_shape[1:])
    _elems = partial(_kv_elems, tail=kv_shape[-2:])

    def update_elems(comp, rest):
        # dynamic-update-slice(%operand, %update, %idx...): the update
        ops = re.findall(r"%([\w.\-]+)", rest)
        return _elems(shapes[comp].get(ops[1], "")) if len(ops) > 1 else 0

    def moves(comp, name, shape, op, rest):
        if "S(1)" in shape:
            # memory space 1: the compiler stages the operand of a
            # contraction on the chip (a layer of K, like a layer's weight
            # matrix, read from HBM once); its byte counts leave it out
            return False
        if op in ("copy", "dynamic-slice"):
            return _elems(shape) >= layer_elems
        if op == "dynamic-update-slice":
            return update_elems(comp, rest) >= layer_elems
        if op == "fusion":
            inner = _CALLED.search(rest).group(1)
            for n, sh, iop, irest in comps.get(inner, []):
                if iop == "dynamic-slice" and _elems(shape) >= layer_elems \
                        and _elems(sh) >= layer_elems:
                    return True
                if iop == "dynamic-update-slice" \
                        and update_elems(inner, irest) >= layer_elems:
                    return True
        return False

    # every computation a while loop runs, through calls and nested loops
    # but not into fusions (a fusion's inside never touches memory itself)
    todo = [c for ins in comps.values() for _, _, op, rest in ins
            if op == "while" for c in _CALLED.findall(rest)]
    looped = set()
    while todo:
        c = todo.pop()
        if c in looped or c not in comps:
            continue
        looped.add(c)
        todo += [x for _, _, op, rest in comps[c] if op != "fusion"
                 for x in _CALLED.findall(rest)]
    return [f"{c}: %{name} = {shape} {op}"
            for c in sorted(looped) for name, shape, op, rest in comps[c]
            if moves(c, name, shape, op, rest)]


def _assert_one_pack_each_way(text: str, slots: int, block_len: int):
    """A decode block crosses the host-device boundary once each way (ISSUE
    38): after the weights and the cache its parameters are the round's
    host rows as ONE packed ``s32[6, slots]`` (where it took six [slots]
    rows, two of them float32) and the keys; what the host reads leaves as
    ONE ``s32[slots, block_len + 1]`` (tokens and counts side by side),
    beside the cache; and the unpack is small fusions over the pack alone:
    none of them reads or writes anything a leaf's size."""
    lines = text[text.index("\nENTRY "):].splitlines()
    own = [l.strip() for l in lines if " parameter(" in l
           and not re.search(r"%(?:params|cache)__", l)]
    assert len(own) == 2, own
    pack = [l for l in own if re.search(rf"= s32\[6,{slots}\]", l)]
    assert len(pack) == 1 and "u32[" in "".join(set(own) - set(pack)), own
    assert not [l for l in lines if " parameter(" in l
                and re.search(rf"= f32\[{slots}\]", l)]
    outs = next(l for l in lines
                if l.strip().startswith("ROOT")).split(" tuple(")[0]
    assert len(re.findall(rf"s32\[{slots},{block_len + 1}\]", outs)) == 1, \
        outs
    # the one [slots] row that still leaves is the cache's lengths
    assert len(re.findall(rf"s32\[{slots}\]", outs)) == 1, outs
    name = re.match(r"%([\w.\-]+) =", pack[0]).group(1)
    users = [l.strip().split(" = ", 1)[1] for l in lines
             if re.search(rf"[(,] ?%{re.escape(name)}[,)]", l)]
    assert users
    for l in users:
        result, operands = l.split("(%", 1)
        assert result.endswith(" fusion") \
            and operands.startswith(name + ")"), l[:200]
        assert all(math.prod(int(d) for d in dims.split(",") if d)
                   <= 6 * slots for dims in _SHAPE.findall(result)), l[:200]


# (hidden, heads, kv heads, FFN, vocab): SmolLM-1.7B's 32 heads of 64, two
# to a lane row of the cache, and Mistral-7B-v0.3's 8 kv heads of 128, one
GEOMETRY = {"smollm": (HID, HEADS, HEADS, FFN, VOCAB),
            "mistral": (4096, 32, 8, 14336, 32768)}


def _serving_program(topo, prog, layout, geometry="smollm", bucket=None,
                     layers=SERVE_LAYERS, slots=SERVE_SLOTS):
    """(lowered-and-compiled ``prog`` of a ``layers``-layer engine of
    ``slots`` slots at one of ``GEOMETRY``'s head geometries on one
    described chip, the abstract cache it was compiled for). Layout
    "kernel" is the contiguous cache under ``attend_impl: flash`` (the
    caller steers ``on_tpu``, so the kernels are compiled and not their
    interpreter), with a head of 1,024 rows: sampling over the whole
    vocabulary is four fifths of a decode block's compile time and no part
    of what is read here."""
    from picotron_tpu.config import Config
    from picotron_tpu.inference.engine import InferenceEngine
    from picotron_tpu.models import llama
    from picotron_tpu.topology import build_topology, named_shardings

    hid, heads, kv_heads, ffn, vocab = GEOMETRY[geometry]
    if layout == "kernel":
        vocab = 1024
    cfg = Config.from_dict({
        "model": dict(hidden_size=hid, intermediate_size=ffn,
                      num_attention_heads=heads, num_key_value_heads=kv_heads,
                      vocab_size=vocab, num_hidden_layers=layers,
                      max_position_embeddings=SEQ, dtype="bfloat16"),
        "inference": {"kv_layout": "paged" if layout == "paged"
                      else "contiguous", "kv_page_len": PAGE,
                      "attend_impl": "flash" if layout == "kernel"
                      else "dense"}})
    mesh = build_topology(1, 1, 1, 1, devices=topo.devices)
    eng = InferenceEngine(cfg, mesh, slots=slots, max_seq_len=SEQ)

    def abstract(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, named_shardings(mesh, specs))

    params = abstract(jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg.model)), eng._pspecs)
    cache = abstract(jax.eval_shape(eng._init_cache_jit), eng._cspecs)
    rep = named_shardings(mesh, jax.sharding.PartitionSpec())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    B = slots
    if prog == "prefill":  # the one-shot prefill of one power-of-two bucket
        args = (arg((1, bucket), I32), arg((1,), I32))
        if eng.sample_on_device:
            args += (arg((2,), jnp.uint32), arg((1,), F32), arg((1,), I32),
                     arg((1,), F32))
        return eng._prefill_jit.lower(params, *args).compile(), cache
    if prog == "decode_block":
        jitted = eng._program("decode_block")
        args = (arg((6, B), I32), arg((eng.decode_block_len, 2), jnp.uint32))
    else:
        jitted = eng._prefill_chunk_jit
        args = (arg((1, eng.prefill_chunk), I32),) + (arg((), I32),) * 3
        if eng.sample_on_device:
            args += (arg((2,), jnp.uint32), arg((1,), F32), arg((1,), I32),
                     arg((1,), F32))
    return jitted.lower(params, cache, *args).compile(), cache


def _assert_leaves_lie_row_major_uncopied(text: str, kv: tuple):
    """The program takes K and V (leaves of shape ``kv``) row-major and no
    instruction of it, inside a loop or outside, copies a whole leaf."""
    leaf = "bf16\\[" + ",".join(map(str, kv)) + "\\]"
    lines = text.splitlines()
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= {leaf}\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    params = [l for l in lines if re.search(rf"cache__[kv]__\S* = {leaf}", l)
              and " parameter(" in l]
    assert len(params) == 2, params
    assert all("{4,3,2,1,0" in l for l in params), params


@pytest.mark.parametrize("prog,layout,geometry", [
    ("decode_block", "contiguous", "smollm"),
    ("prefill_chunk", "contiguous", "smollm"),
    ("decode_block", "contiguous", "mistral"),
    ("prefill_chunk", "contiguous", "mistral"),
    ("decode_block", "kernel", "smollm"), ("decode_block", "kernel", "mistral"),
    ("decode_block", "paged", "smollm"), ("prefill_chunk", "paged", "smollm")])
def test_serving_program_leaves_cache_in_place(prog, layout, geometry, topo,
                                               one_chip, monkeypatch):
    """No loop of the program moves a layer of K or V through HBM, and the
    temporaries stay small. The contiguous cache besides lies row-major,
    whole lanes a row, and no instruction copies a leaf of it: heads of 64
    two to a row (with a head a row the resident leaf was laid out tokens
    minor-most and converted on the program's entry and exit: ``copy.18`` /
    ``.19`` / ``.25`` / ``.26`` of PR 30's trace, outside every loop), heads
    of 128 one (``pack_factor`` 1: the leaf they always had, which
    ``cache_write`` holds row-major like the packed one since PR 64: left
    free, Mistral's chunk re-laid both leaves on entry and on exit).
    With the kernel forced ("kernel": what ``attend_impl: auto`` runs on a
    TPU) the decode step hands the stacked leaves to ``flash_decode_stacked``
    as they lie: one custom call a layer, and nothing in front of it that
    slices a layer out or unpacks a row."""
    if layout == "kernel":
        from picotron_tpu.inference import kv_cache

        monkeypatch.setattr(kv_cache, "on_tpu", lambda: True)
    compiled, cache = _serving_program(topo, prog, layout, geometry)
    text, kv = compiled.as_text(), cache["k"].shape
    if layout == "kernel":
        assert text.count("custom_call_target=\"tpu_custom_call\"") == 1, \
            "the decode block's layer loop holds one flash-decode kernel"
    if prog == "decode_block":
        _assert_one_pack_each_way(text, SERVE_SLOTS, 8)
    movers = _cache_movers(text, kv)
    assert not movers, (
        f"{prog}/{layout}: a loop of the compiled program moves a whole "
        "layer of the KV cache or more at once:\n" + "\n".join(movers))
    temp = compiled.memory_analysis().temp_size_in_bytes
    kv_bytes = 2 * math.prod(kv) * 2  # K and V, bf16, as stored
    if layout == "paged":  # the pool keeps a head a row, lane-padded
        limit, what = 1.5 * kv_bytes * max(kv[-1], 128) / kv[-1], \
            "1.5 lane-padded pools"
    elif prog == "decode_block":
        limit, what = kv_bytes, "one cache"
    else:  # the chunk's float32 scores, 512 x 2048 x 32 heads: 134 MB
        limit, what = 4 * 512 * SEQ * GEOMETRY[geometry][1] \
            + 0.5 * kv_bytes, "the chunk's scores and half a cache"
    assert temp < limit, (
        f"{prog}/{layout}: {temp / 1e6:.0f} MB of temporaries against "
        f"{what}, {limit / 1e6:.0f} MB")
    if layout == "paged":
        return
    # 32 heads of 64 two to a row; 8 heads of 128 as they always lay
    assert kv[-2:] == {"smollm": (16, 128), "mistral": (8, 128)}[geometry]
    _assert_leaves_lie_row_major_uncopied(text, kv)


@pytest.mark.parametrize("prog,layout", [("prefill_chunk", "contiguous"),
                                         ("decode_block", "kernel")])
def test_mistral_at_the_cells_size_copies_no_leaf(prog, layout, topo,
                                                  one_chip, monkeypatch):
    """The two programs of Mistral's serving cells at the cells' own cache
    (16 layers x 8 slots x 2,048: ``bf16[16,8,2048,8,128]``, 537 MB a leaf)
    under ``attend_impl: auto`` as a TPU takes it: the chunk attends densely,
    the decode block through the stacked kernel. Neither holds an instruction
    that copies a leaf, inside a loop or outside. PR 63's chunk held four in
    its ENTRY computation (K re-laid tokens-minor and V heads-major on entry,
    both back on exit: 4.3 GB of HBM traffic a chunk of whatever width, 1,074
    MB of temporaries); what is left of its temporaries is the chunk's
    float32 scores (135 MB)."""
    from picotron_tpu.inference import kv_cache

    if layout == "kernel":
        monkeypatch.setattr(kv_cache, "on_tpu", lambda: True)
    compiled, cache = _serving_program(topo, prog, layout, "mistral",
                                       layers=16, slots=8)
    text, kv = compiled.as_text(), cache["k"].shape
    assert kv == (16, 8, SEQ, 8, 128)
    assert not _cache_movers(text, kv)
    _assert_leaves_lie_row_major_uncopied(text, kv)
    if prog == "prefill_chunk":
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 0.3e9, f"{temp / 1e6:.0f} MB of temporaries"


@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
def test_decode_step_holds_one_op_of_the_readers_name_a_layer(
        geometry, topo, one_chip, monkeypatch):
    """The five dense-form roofline readers (``benchmarks/layer_metrics/
    kernels.flash_decode_roofline*.py``, ``.block_decode_roofline.sdar``,
    ``.full_decode_roofline.mimo``) divide a layer's live K/V bytes by the
    mean device time of the ops whose name ends in ``flash_decode_attention``:
    the decode block's compiled program holds exactly one instruction of
    that name, a custom call in the body of the loop over the layers, and the
    attend is no second kernel beside it."""
    from picotron_tpu.inference import kv_cache

    monkeypatch.setattr(kv_cache, "on_tpu", lambda: True)
    compiled, _ = _serving_program(topo, "decode_block", "kernel", geometry)
    comps = _computations(compiled.as_text())
    named = [(c, n, op) for c, ins in comps.items() for n, _, op, _ in ins
             if re.sub(r"[.\d]+$", "", n).endswith("flash_decode_attention")]
    assert len(named) == 1 and named[0][2] == "custom-call", named
    kernels = [n for ins in comps.values() for n, _, op, rest in ins
               if op == "custom-call" and "\"tpu_custom_call\"" in rest]
    assert kernels == [named[0][1]], kernels
    bodies = [rest for ins in comps.values() for _, _, op, rest in ins
              if op == "while"]
    assert any(f"body=%{named[0][0]}" in rest for rest in bodies), \
        (named, [b[-120:] for b in bodies])


# --------------------------------------------------------------------------- #
# the prefix store's two copy programs (ISSUE 49)
# --------------------------------------------------------------------------- #


def _store_engine(topo, geometry, store_pages=0):
    """(engine on one described chip at one of ``GEOMETRY``'s head
    geometries, its abstract cache, its abstract page pool or None)."""
    from picotron_tpu.config import Config
    from picotron_tpu.inference.engine import InferenceEngine
    from picotron_tpu.topology import build_topology, named_shardings

    hid, heads, kv_heads, ffn, vocab = GEOMETRY[geometry]
    cfg = Config.from_dict({
        "model": dict(hidden_size=hid, intermediate_size=ffn,
                      num_attention_heads=heads, num_key_value_heads=kv_heads,
                      vocab_size=1024, num_hidden_layers=SERVE_LAYERS,
                      max_position_embeddings=SEQ, dtype="bfloat16"),
        "inference": {"attend_impl": "dense"}})
    mesh = build_topology(1, 1, 1, 1, devices=topo.devices)
    eng = InferenceEngine(cfg, mesh, slots=SERVE_SLOTS, max_seq_len=SEQ,
                          kv_store_pages=store_pages)
    full = named_shardings(mesh, eng._cspecs)
    abstract = lambda tree: {
        n: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=full[n])
        for n, x in tree.items()}
    cache = abstract(jax.eval_shape(eng._init_cache_jit))
    pool = (abstract(jax.eval_shape(eng._init_store_jit))
            if eng.store is not None else None)
    return eng, cache, pool


@pytest.mark.parametrize("geometry", sorted(GEOMETRY))
@pytest.mark.parametrize("prog", ["retain", "seat"])
def test_store_copy_moves_a_strip_and_nothing_else(prog, geometry, topo,
                                                   one_chip):
    """Retention (strip -> pool) and the hit (pool -> strip) compile for
    the chip as one gather or scatter of pages over the pool seen as ONE
    axis of layer x page, in place: the donated side is aliased to the
    result, nothing is transposed, no array of a strip's size or more is
    copied, and the program's temporaries stay under one strip (the flat
    index; the rows themselves are staged on the chip). SmolLM's packed
    rows (two heads of 64 a lane row) are pages of the same rows."""
    eng, cache, pool = _store_engine(topo, geometry)
    rep = jax.sharding.NamedSharding(eng.topo.mesh,
                                     jax.sharding.PartitionSpec())
    arg = lambda shape: jax.ShapeDtypeStruct(shape, I32, sharding=rep)
    pids = arg((eng.store.max_pages,))
    assert pool["k"].shape == (SERVE_LAYERS, 1 + 2 * SERVE_SLOTS * MAXP * (
        PAGE // eng.store.page_len), eng.store.page_len) + cache["k"].shape[3:]
    if prog == "retain":
        compiled = eng._retain_jit.lower(pool, cache, arg(()), pids).compile()
        moved, kept = pool, "pool"
    else:
        compiled = eng._seat_jit.lower(cache, pool, arg(()), pids,
                                       arg(())).compile()
        moved, kept = cache, "cache"
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    strip = 2 * math.prod(cache["k"].shape) // SERVE_SLOTS * 2  # K, V, bf16
    donated = 2 * math.prod(moved["k"].shape) * 2
    assert mem.alias_size_in_bytes >= donated, (
        f"{prog}: the {kept} is not updated in place")
    assert mem.temp_size_in_bytes < strip, (
        f"{prog}: {mem.temp_size_in_bytes / 1e6:.0f} MB of temporaries "
        f"against a strip of {strip / 1e6:.0f} MB")
    turned = [l.strip()[:160] for l in text.splitlines()
              if (m := re.search(r" transpose\(.*dimensions=\{([\d,]*)\}", l))
              and m.group(1) != ",".join(
                  map(str, range(m.group(1).count(",") + 1)))]
    assert not turned, "\n".join(turned)
    copies = [l.strip()[:160] for l in text.splitlines()
              if (m := _INSTR.match(l)) and m.group(3) == "copy"
              and max((math.prod(int(d) for d in dims.split(",") if d)
                       for dims in _SHAPE.findall(m.group(2))), default=0)
              >= strip // 4]
    assert not copies, "\n".join(copies)
    assert len(re.findall(r" (?:gather|scatter)\(", text)) == 2, (
        "one gather or scatter a leaf")


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_store_leaves_the_serving_programs_as_they_were(prog, topo,
                                                        one_chip):
    """The decode and chunk programs of an engine that keeps a store lower
    to the text of one that keeps none: the store is two programs beside
    them and an operand of neither."""
    from picotron_tpu.models import llama
    from picotron_tpu.topology import named_shardings

    texts = []
    for store_pages in (0, -1):
        eng, cache, _ = _store_engine(topo, "mistral", store_pages)
        assert (eng.store is None) == (store_pages < 0)
        mesh = eng.topo
        params = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            jax.eval_shape(lambda: llama.init_params(jax.random.key(0),
                                                     eng.cfg.model)),
            named_shardings(mesh, eng._pspecs))
        rep = named_shardings(mesh, jax.sharding.PartitionSpec())
        arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
        if prog == "decode_block":
            jitted = eng._program("decode_block")
            args = (arg((6, SERVE_SLOTS), I32),
                    arg((eng.decode_block_len, 2), jnp.uint32))
        else:
            jitted = eng._prefill_chunk_jit
            args = (arg((1, eng.prefill_chunk), I32),) + (arg((), I32),) * 3
        texts.append(jitted.lower(params, cache, *args).as_text())
    assert texts[0] == texts[1]


# --------------------------------------------------------------------------- #
# train-2k's layer: q, k, v and their gradients are never copied whole
# --------------------------------------------------------------------------- #

_QKV_SIZED = re.compile(
    r"= bf16\[(?:3,2048,32,64|3,32,2048,64|96,2048,64|3,2048,2048)\]\S* "
    r"(copy|transpose)\((?!%gather|%reshape)")


def _train_layer_text(topo, monkeypatch, heads_per_row=None):
    """Compiled text of the train-2k cell's own layer, forward and gradient
    under ``remat: full`` (micro-batch 3 of SmolLM-1.7B at 2048), on one
    described chip; ``heads_per_row`` overrides the rule's answer."""
    from jax.sharding import PartitionSpec as P

    from picotron_tpu.config import Config
    from picotron_tpu.models import llama
    from picotron_tpu.topology import build_topology, named_shardings
    from picotron_tpu.utils import shard_map

    monkeypatch.setattr(llama, "on_tpu", lambda: True)
    if heads_per_row is not None:
        monkeypatch.setattr(llama, "flash_heads_per_row",
                            lambda cfg: heads_per_row)
    hid, heads, kv_heads, ffn, vocab = GEOMETRY["smollm"]
    cfg = Config.from_dict({
        "model": dict(hidden_size=hid, intermediate_size=ffn,
                      num_attention_heads=heads, num_key_value_heads=kv_heads,
                      vocab_size=vocab, num_hidden_layers=1,
                      max_position_embeddings=SEQ, dtype="bfloat16"),
        "training": {"seq_length": SEQ, "micro_batch_size": 3,
                     "remat": "full"},
        "dataset": {"name": "synthetic"}})
    mesh = build_topology(1, 1, 1, 1, devices=topo.devices)
    specs = llama.param_pspecs(cfg.model)["layers"]
    cos, sin = llama.rope_tables(cfg)

    def loss(params, h):
        return _sum32(llama.layers_forward(params, h, cos, sin, cfg))

    def abstract(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, named_shardings(mesh, specs))

    params = abstract(jax.eval_shape(lambda: llama.init_params(
        jax.random.key(0), cfg.model))["layers"], specs)
    h = abstract(jax.ShapeDtypeStruct((3, SEQ, hid), BF16), P())
    fn = shard_map(jax.grad(loss, argnums=(0, 1)), mesh.mesh,
                   in_specs=(specs, P()), out_specs=(specs, P()))
    return jax.jit(fn).lower(params, h).compile().as_text()


def test_train_layer_never_copies_qkv(topo, one_chip, monkeypatch):
    """ISSUE 48: at heads of 64 the layer's flash kernels take q, k, v and
    dO as rows [3, 2048, 32 * 64], where the projections and ``rope_rows``
    leave them, and hand back the output and the gradients the same way: no
    ``copy`` or ``transpose`` of a q-sized array in the compiled layer,
    where the folded call's fold and unfold are a dozen of them. The
    kernels keep their names and their count: the forward (here the
    ``remat: full`` recompute alone: a gradient needs no first pass) and
    ONE backward, ``rope_rows`` in front of the one and behind the other."""
    text = _train_layer_text(topo, monkeypatch)
    moved = [l.strip()[:140] for l in text.splitlines()
             if _QKV_SIZED.search(l)]
    assert not moved, "\n".join(moved)
    calls = re.findall(r"%(\w+?)(?:\.\d+)? = [^=]*custom-call\([^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert [calls.count(k) for k in
            ("flash_fwd", "flash_bwd_dkv", "rope_rows")] == [1, 1, 2], calls
    assert "bf16[96,2048,64]" not in text


def test_folded_train_layer_copies_qkv(topo, one_chip, monkeypatch):
    """The same layer a head a row (what it compiled to before PR 48, and
    what heads of 128 still do): the copies the test above would see."""
    text = _train_layer_text(topo, monkeypatch, heads_per_row=1)
    moved = [l for l in text.splitlines() if _QKV_SIZED.search(l)]
    assert len(moved) >= 8, "\n".join(l.strip()[:140] for l in moved)
    assert "rope_rows" not in text and "bf16[96,2048,64]" in text


def test_smollm_prefill_keeps_the_folded_call(topo, one_chip, monkeypatch):
    """The serving engine's one-shot prefill (``return_kv=True``) at bucket
    256 of SmolLM still holds ``flash_fwd`` over [32, 256, 64] and no
    ``rope_rows``: its programs are the parent's, and so is the serving
    cell's set-up (five such programs a process; PERF.md, PR 47)."""
    from picotron_tpu.models import llama

    monkeypatch.setattr(llama, "on_tpu", lambda: True)
    text = _serving_program(topo, "prefill", "contiguous",
                            bucket=256)[0].as_text()
    kernel = [l for l in text.splitlines() if re.search(r"%flash_fwd\S* = ", l)]
    assert len(kernel) == 1 and "bf16[32,256,64]" in kernel[0], kernel
    assert "rope_rows" not in text


@pytest.fixture
def experts_on_chip(monkeypatch):
    """A chunk's rows past the rule (``experts.takes_grouped``) go through
    the Mosaic kernel, as on a TPU; off one they run Pallas' interpreter."""
    from picotron_tpu.models import experts

    monkeypatch.setattr(experts, "on_tpu", lambda: True)


@pytest.fixture
def ssm_on_chip(monkeypatch):
    """A recurrent layer's decode step takes the Pallas kernel on its row of
    the stacked state leaf, as on a TPU; off one it is the elementwise step
    between a slice and an update."""
    from picotron_tpu.ops import ssm

    monkeypatch.setattr(ssm, "on_tpu", lambda: True)


@pytest.fixture
def kda_on_chip(monkeypatch):
    """The delta rule's decode step takes the Pallas kernel on its row of
    the stacked state leaf, as on a TPU; off one it is the step written out
    between a slice and an update."""
    from picotron_tpu.ops import kda

    monkeypatch.setattr(kda, "on_tpu", lambda: True)


def _assert_state_steps_in_place(text: str, prog: str, state: str,
                                 kernel: str = "ssm_step", operand: int = 5):
    """ISSUE 57, ISSUE 59: a decode block holds the ``ssm_step`` (or
    ``kda_step``) kernel, the state leaf (``state``: its shape as the text
    writes it) its operand and, aliased, its result, so neither a slice of
    a layer's row in front of it nor an update behind it; a chunk scans and
    holds none."""
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and f"/{kernel}/pallas_call" in l]
    assert bool(calls) == (prog == "decode_block"), len(calls)
    for call in calls:
        assert re.search(rf"= \({state}\S*, f32\[", call), call[:200]
        assert "output_to_operand_aliasing={{0}: (%d, {})}" % operand \
            in call, call[:400]
    if calls:
        rows = state.replace(r"f32\[", "").split(",", 1)[1]
        moved = [l.strip()[:160] for l in text.splitlines()
                 if re.search(rf"= f32\[(?:1,)?{rows}\S* "
                              r"(?:fusion|copy|dynamic-slice)\(", l)]
        assert not moved, "\n".join(moved)


def _assert_expert_orders(text: str, prog: str, pipelined: bool,
                          wide: bool = False):
    """ISSUE 43: the 512-row chunk runs each held expert over its own rows
    (the kernel reads the stacks where they lie: the callers' ``sliced``
    lists hold it to that). ISSUE 44: the decode block's rows take the
    pipelined pass where ``experts.takes_pipelined`` says so (Granite's
    shape: ``pipelined``), one Pallas call a layer and no scan over the
    held experts beside it, and keep that scan where it does not
    (DeepSeek's, Trinity's, MiMo's). ``wide``: the program holds a forward
    past the rule's ridge beside its narrow ones (a round of blocks' fused
    forward, 256 rows), which runs grouped too."""
    chunk = prog == "prefill_chunk"
    assert ("moe_experts/grouped/grouped_experts" in text) == (chunk or wide)
    assert ("moe_experts/pipelined/pipelined_experts" in text) == (
        pipelined and not chunk)
    # the loop's scan carries the held experts' weights a row ([held, rows]
    # float32) and their indices, then the stacks [layers, held, ...]
    loops = [l.strip()[:160] for l in text.splitlines()
             if " while(" in l and re.search(
                 r"f32\[(\d+),\d+\]\{[^}]*\}, s32\[\1\]\{[^}]*\}, "
                 r"(?:/\*index=\d+\*/)?bf16\[\d+,\1,\d+,\d+\]", l)]
    assert bool(loops) == (not pipelined and not chunk), "\n".join(loops)


# ---- the latent cache of the DeepSeek-V3.2 block (PR 28) -------------------


def _latent_program(topo, prog):
    """``prog`` of a 2-layer engine (one dense, one expert layer) at the
    published DeepSeek-V3.2 widths and the benchmark cell's cache geometry
    (8 slots x 24576), compiled for one described chip."""
    import json

    from picotron_tpu.config import Config
    from picotron_tpu.inference.engine import InferenceEngine
    from picotron_tpu.topology import build_topology, named_shardings

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "deepseek-v3.2-ep32-l7.json")) as f:
        pub = json.load(f)
    model = {k: pub[k] for k in pub["model_keys"]}
    model.update({k: pub[k] for k in (
        "num_attention_heads", "num_key_value_heads", "hidden_size",
        "intermediate_size", "vocab_size", "rms_norm_eps", "rope_theta",
        "max_position_embeddings")}, num_hidden_layers=2, dtype="bfloat16")
    cfg = Config.from_dict({"model": model,
                            "training": {"seq_length": 24576}})
    mesh = build_topology(1, 1, 1, 1, devices=topo.devices)
    eng = InferenceEngine(cfg, mesh, slots=8, max_seq_len=24576)

    def abstract(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, named_shardings(mesh, specs))

    params = abstract(jax.eval_shape(
        lambda: eng.model.init_params(jax.random.key(0), cfg.model)),
        eng._pspecs)
    cache = abstract(jax.eval_shape(eng._init_cache_jit), eng._cspecs)
    rep = named_shardings(mesh, jax.sharding.PartitionSpec())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    if prog == "decode_block":
        jitted = eng._program("decode_block")
        args = (arg((6, 8), I32), arg((eng.decode_block_len, 2), jnp.uint32))
    else:
        jitted = eng._prefill_chunk_jit
        args = (arg((1, eng.prefill_chunk), I32),) + (arg((), I32),) * 3
    return jitted.lower(params, cache, *args).compile()


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_latent_cache_is_row_major_and_never_copied(prog, topo, one_chip,
                                                    experts_on_chip):
    """The TPU lays a cache leaf whose rows are not whole lanes (64 wide, or
    576) out with the tokens minor-most and copies it whole, in the layer
    loop or at the program's entry and exit (PR 28 read both here): the
    lane-padded ``[c_kv | k_r]`` row stays row-major, and no instruction
    copies a whole leaf; nor is a layer's slice of the experts' stacks
    copied out before the loop over experts or the grouped kernel."""
    text = _latent_program(topo, prog).as_text()
    _assert_expert_orders(text, prog, pipelined=False)
    leaf = r"bf16\[2,8,24576,(?:640|128)\]"
    lines = text.splitlines()
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= {leaf}\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    params = [l for l in lines if re.search(rf"cache__c?k[vi]__\S* = {leaf}", l)
              and " parameter(" in l]
    assert len(params) == 2, params
    assert all("{3,2,1,0" in l for l in params), params
    sliced = [l.strip()[:160] for l in lines
              if re.search(r"= bf16\[(?:1,)?8,(?:7168,2048|2048,7168)\]", l)
              and " parameter(" not in l and "get-tuple-element" not in l]
    assert not sliced, "\n".join(sliced)
    # a decode step GATHERS the rows it chose (one gather of 16,384 latent
    # rows a layer) and never slices a key block of latent rows out of the
    # leaf; a chunk walks the live key blocks under its mask and never gathers
    gathers = [l for l in lines
               if re.search(r"= bf16\[8,2048,(?:1,)?640\]\S* gather\(", l)]
    assert (len(gathers) == 2) == (prog == "decode_block"), gathers
    walked = [l for l in lines if re.search(
        r"%\S*dynamic-slice\S* = bf16\[(?:1,)?[18],2048,640\]", l)]
    assert bool(walked) == (prog == "prefill_chunk"), walked[:4]


# ---- the recurrent state of the Granite-4.0-H block (PR 32) -----------------


def _cell_program(topo, prog, name):
    """``prog`` of a benchmark cell's own engine: configuration ``name`` at
    its published widths and its cell's slots and window, compiled for one
    described chip (Granite's ten layers: at three the compiler left the
    state where it lay, pinned or not)."""
    import json

    from picotron_tpu.config import Config
    from picotron_tpu.inference.engine import InferenceEngine
    from picotron_tpu.topology import build_topology, named_shardings

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           name + ".json")) as f:
        pub = json.load(f)
    model = {k: pub[k] for k in pub["model_keys"]}
    model.update({k: pub[k] for k in (
        "num_attention_heads", "num_key_value_heads", "hidden_size",
        "intermediate_size", "vocab_size", "rms_norm_eps", "rope_theta",
        "max_position_embeddings", "num_hidden_layers")}, dtype="bfloat16")
    slots, window = pub["serve"]["slots"], pub["serve"]["max_seq_len"]
    cfg = Config.from_dict({"model": model,
                            "training": {"seq_length": window}})
    mesh = build_topology(1, 1, 1, 1, devices=topo.devices)
    eng = InferenceEngine(cfg, mesh, slots=slots, max_seq_len=window)

    def abstract(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, named_shardings(mesh, specs))

    params = abstract(jax.eval_shape(
        lambda: eng.model.init_params(jax.random.key(0), cfg.model)),
        eng._pspecs)
    cache = abstract(jax.eval_shape(eng._init_cache_jit), eng._cspecs)
    rep = named_shardings(mesh, jax.sharding.PartitionSpec())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    if prog == "decode_block":
        jitted = eng._program("decode_block")
        args = (arg((6, slots), I32),
                arg((eng.decode_block_len, 2), jnp.uint32))
    elif prog == "blocks":  # a round of blocks: the waiting block's tokens
        jitted = eng._program("blocks")  # and a block's, then ``given``
        args = (arg((2 * cfg.model.block_length + 6, slots), I32),
                arg((eng.decode_block_len, 2), jnp.uint32))
    else:
        jitted = eng._prefill_chunk_jit
        args = (arg((1, eng.prefill_chunk), I32),) + (arg((), I32),) * 3
    return jitted.lower(params, cache, *args).compile()


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_recurrent_state_is_row_major_and_never_copied(prog, topo, one_chip,
                                                       experts_on_chip,
                                                       ssm_on_chip):
    """Left free, a prefill chunk's contractions pulled the whole float32
    state leaf (2.4 GB) into their own order on entry and pushed it back on
    exit (PR 32 read both copies here, 2.46 GB of temporaries, 15.5 GB in
    all; ``kv_cache.row_major``): the leaf stays row-major, no instruction copies it, and
    K and V of the attention layer stay in place beside it; nor is a layer's
    slice of the experts' stacks (680 MB) copied out before the pipelined
    pass over the experts (ISSUE 44) or, in the chunk, the grouped kernel
    (ISSUE 43), whose VMEM the compiler grants beside what it stages there
    itself."""
    compiled = _cell_program(topo, prog, "granite-4.0-h-small-ep2-l10")
    text = compiled.as_text()
    _assert_expert_orders(text, prog, pipelined=True)
    lines = text.splitlines()
    state = r"f32\[9,64,128,64,128\]"
    kv = r"bf16\[1,64,4096,8,128\]"
    _assert_state_steps_in_place(text, prog, state)
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= (?:{state}|{kv})\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    params = [l for l in lines if re.search(rf"cache__ssm__\S* = {state}", l)
              and " parameter(" in l]
    assert len(params) == 1 and "{4,3,2,1,0" in params[0], params
    # resident: 9.51 GB of weights + 3.52 GB of cache; a chunk's
    # temporaries read 0.36 GB, a block's 0.08 (a layer's state is 0.27)
    assert compiled.memory_analysis().temp_size_in_bytes < 500e6
    sliced = [l.strip()[:160] for l in lines
              if re.search(r"= bf16\[(?:1,)?36,(?:4096,768|768,4096)\]", l)
              and " parameter(" not in l and "get-tuple-element" not in l]
    assert not sliced, "\n".join(sliced)


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_nemotron_state_and_packed_kv_stay_in_place(prog, topo, one_chip,
                                                    experts_on_chip,
                                                    ssm_on_chip):
    """The Nemotron-H cell's programs at its own size (PR 56: 128 slots x
    8192 beside 9.30 GB of weights): the float32 state leaf (2.68 GB) and
    the K/V leaves, two heads of 128 a token under the compiler's own (2,
    128) tile, stay row-major and no instruction copies them (the two heads
    side by side in one 256-lane row were padded to two rows and copied
    whole, three times a program); a decode block runs the 128 held two-matrix
    experts as the pipelined pass, a chunk as the grouped kernel, neither
    behind a copy of a layer's slice of the stacks (1.41 GB); and the
    programs' temporaries (0.60 and 0.54 GB) leave the resident 13.09 GB
    its room."""
    compiled = _cell_program(topo, prog, "nemotron-3-super-ep4-l11")
    text = compiled.as_text()
    _assert_expert_orders(text, prog, pipelined=True)
    lines = text.splitlines()
    state = r"f32\[5,128,128,64,128\]"
    kv = r"bf16\[1,128,8192,2,128\]"
    _assert_state_steps_in_place(text, prog, state)
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= (?:{state}|{kv})\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    for leaf, shape in (("ssm", state), ("k", kv), ("v", kv)):
        params = [l for l in lines
                  if re.search(rf"cache__{leaf}__\S* = {shape}", l)
                  and " parameter(" in l]
        assert len(params) == 1 and "{4,3,2,1,0" in params[0], params
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.8e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 14.0e9
    sliced = [l.strip()[:160] for l in lines
              if re.search(r"= bf16\[(?:1,)?128,(?:1024,2688|2688,1024)\]", l)
              and " parameter(" not in l and "get-tuple-element" not in l]
    assert not sliced, "\n".join(sliced)


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_solar_state_and_kv_stay_in_place(prog, topo, one_chip,
                                          experts_on_chip, kda_on_chip):
    """The Solar Open 2 cell's programs at its own size (PR 58: 64 slots x
    8192 beside 7.80 GB of weights): the float32 delta-rule state leaf (1.61
    GB) and the K/V leaves of eight heads of 128 stay row-major and no
    instruction copies them; a decode block's KDA layer steps its row of
    the state through the ``kda_step`` kernel, the leaf its operand and
    aliased result (ISSUE 59: the two fusions that walked the row three
    times are gone, with the slice in front of them and the update behind),
    and never lands a layer's state (268 MB) in a buffer of its own; the 20
    held experts of 1,280 at 4,096 (31.5 MB each, past the pass's weight
    budget) take the loop in the block and the grouped kernel in the chunk,
    neither behind a copy of a layer's slice of the stacks (0.63 GB); and
    the programs' temporaries leave the resident 13.76 GB its room in the
    chip's 15.75."""
    compiled = _cell_program(topo, prog, "solar-open2-ep16-l8")
    text = compiled.as_text()
    _assert_expert_orders(text, prog, pipelined=False)
    lines = text.splitlines()
    state = r"f32\[6,64,64,128,128\]"
    kv = r"bf16\[2,64,8192,8,128\]"
    _assert_state_steps_in_place(text, prog, state, "kda_step", 6)
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= (?:{state}|{kv})\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    for leaf, shape in (("kda", state), ("k", kv), ("v", kv)):
        params = [l for l in lines
                  if re.search(rf"cache__{leaf}__\S* = {shape}", l)
                  and " parameter(" in l]
        assert len(params) == 1 and "{4,3,2,1,0" in params[0], params
    if prog == "decode_block":
        comps = _computations(text)
        own = [f"{c}: %{n} = {sh[:40]} {op}" for c, ins in comps.items()
               if not c.startswith("fused_computation")
               for n, sh, op, _ in ins
               if re.match(r"f32\[(?:1,)?64,64,128,128\]", sh)]
        assert not own, "\n".join(own)
        # nothing but the kernel is handed the leaf: no fusion of a layer
        # step walks it, in either of the two KDA groups' bodies
        walked = []
        for c, ins in comps.items():
            shapes = {n: sh for n, sh, _, _ in ins}
            walked += [f"{c}: %{n}" for n, _, op, rest in ins
                       if op == "fusion" and any(
                           re.match(state, shapes.get(o, ""))
                           for o in re.findall(r"%([\w.\-]+)", rest))]
        assert not walked, walked
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 1.6e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.4e9
    sliced = [l.strip()[:160] for l in lines
              if re.search(r"= bf16\[(?:1,)?20,(?:4096,1280|1280,4096)\]", l)
              and " parameter(" not in l and "get-tuple-element" not in l]
    assert not sliced, "\n".join(sliced)


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_falcon_state_and_kv_of_every_layer_stay_in_place(
        prog, topo, one_chip, ssm_on_chip, monkeypatch):
    """The Falcon-H1 cell's programs at its own size (PR 65: 64 slots x
    6,144 beside 8.79 GB of weights, the whole 261,120-row head among them):
    every layer keeps a K/V row AND a float32 state row, and all four leaves
    stay row-major with no instruction copying one; a decode block steps
    each layer's row of the state through the ``ssm_step`` kernel (32 heads
    of 128 x 256 in two B/C groups), the leaf its operand and aliased
    result, and attends through ``flash_decode_attention`` at five query
    heads a K/V head, one call each a layer in the scanned body; a chunk
    holds neither; and the programs' temporaries leave the resident 13.09 GB
    its room in the chip's 15.75."""
    from picotron_tpu.inference import kv_cache
    from picotron_tpu.models import llama

    monkeypatch.setattr(kv_cache, "on_tpu", lambda: True)
    # the head's norm as on a TPU: the Pallas kernel over 64 rows of 5,120
    monkeypatch.setattr(llama, "on_tpu", lambda: True)
    compiled = _cell_program(topo, prog, "falcon-h1-34b-l4")
    text = compiled.as_text()
    lines = text.splitlines()
    state = r"f32\[4,64,32,128,256\]"
    kv = r"bf16\[4,64,6144,4,128\]"
    _assert_state_steps_in_place(text, prog, state)
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= (?:{state}|{kv})\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    for leaf, shape in (("ssm", state), ("k", kv), ("v", kv)):
        params = [l for l in lines
                  if re.search(rf"cache__{leaf}__\S* = {shape}", l)
                  and " parameter(" in l]
        assert len(params) == 1 and "{4,3,2,1,0" in params[0], params
    kernels = [l for l in lines
               if re.search(r"%flash_decode_attention\S* = ", l)]
    assert len(kernels) == (1 if prog == "decode_block" else 0), kernels
    assert "rmsnorm_fwd" in text
    memory = compiled.memory_analysis()
    assert round(memory.argument_size_in_bytes / 1e9, 2) == 13.09
    assert memory.temp_size_in_bytes < 1.5e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.0e9


def test_trinity_decode_block_keeps_the_loop(topo, one_chip, monkeypatch,
                                             experts_on_chip):
    """The Trinity cell's decode block (16 rows over eight held experts of
    three 18.9 MB matrices each: 113 MB with the pipeline's two buffers,
    past the pipelined pass's whole-matrix budget): the scan over the held
    experts, no Pallas call for them, the stacks read where they lie, the
    rings' kernel beside it."""
    from picotron_tpu.models import afmoe

    monkeypatch.setattr(afmoe, "on_tpu", lambda: True)
    compiled = _cell_program(topo, "decode_block", "trinity-large-ep32-l9")
    text = compiled.as_text()
    _assert_expert_orders(text, "decode_block", pipelined=False)
    assert "flash_decode_ring" in text
    sliced = [l.strip()[:160] for l in text.splitlines()
              if re.search(r"= bf16\[(?:1,)?8,3072,3072\]", l)
              and " parameter(" not in l and "get-tuple-element" not in l]
    assert not sliced, "\n".join(sliced)


# ---- the four leaves of the MiniCPM-SALA block (PR 34) ----------------------


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_sala_cache_leaves_are_never_copied_whole(prog, topo, one_chip,
                                                  ssm_on_chip):
    """K and V a kv head's keys one after the other ([layers, slots, kv
    heads, T, d]): tokens-major, the decode block re-laid both leaves inside
    its step loop (four copies of 0.8 GB a step) and a prefill chunk on
    entry and exit (PR 34 read them here); left free, a chunk's contractions
    still pull V tokens-minor (``kv_cache.row_major`` on the chunk's write).
    No instruction copies K, V, the compressed keys or the float32 state,
    and each stays row-major as it is resident."""
    compiled = _cell_program(topo, prog, "minicpm-sala-l12")
    lines = compiled.as_text().splitlines()
    if prog == "decode_block":  # the cell's 8 slots, 8 tokens a block
        _assert_one_pack_each_way(compiled.as_text(), 8, 8)
    leaves = {"k": r"bf16\[3,8,2,65536,128\]", "v": r"bf16\[3,8,2,65536,128\]",
              "kc": r"bf16\[3,8,2,4096,128\]",
              "state": r"f32\[9,8,32,128,128\]"}
    _assert_state_steps_in_place(compiled.as_text(), prog, leaves["state"])
    shapes = "|".join(sorted(set(leaves.values())))
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= (?:{shapes})\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    for name, shape in leaves.items():
        params = [l for l in lines
                  if re.search(rf"cache__{name}__\S* = {shape}", l)
                  and " parameter(" in l]
        assert len(params) == 1 and "{4,3,2,1,0" in params[0], (name, params)
    # resident: 7.86 GB of weights + 1.81 GB of cache; a decode block's
    # temporaries read 0.92 GB (the lightning layers' q, k, v weights
    # re-laid once a block), a chunk's 0.01
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


# ---- the four leaves of the MiMo-V2 block (PR 42) ---------------------------


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_mimo_cache_leaves_are_never_copied_whole(prog, topo, one_chip,
                                                  monkeypatch,
                                                  experts_on_chip):
    """Four leaves of four shapes, a row's heads merged so that every row is
    whole lanes (a key head of 192 held a head a row would be padded to 256):
    each stays row-major as it is resident, no instruction copies one, the
    decode block holds both forms of the stacked kernel (the full layers'
    and the rings' with its sink), and the programs' temporaries leave the
    13.07 GB resident room on the chip."""
    from picotron_tpu.models import mimo_v2

    monkeypatch.setattr(mimo_v2, "on_tpu", lambda: True)
    compiled = _cell_program(topo, prog, "mimo-v2.5-ep32-l13")
    text = compiled.as_text()
    _assert_expert_orders(text, prog, pipelined=False)
    lines = text.splitlines()
    leaves = {"k": r"bf16\[3,32,16384,768\]", "v": r"bf16\[3,32,16384,512\]",
              "kw": r"bf16\[10,32,640,1536\]",
              "vw": r"bf16\[10,32,640,1024\]"}
    shapes = "|".join(leaves.values())
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= (?:{shapes})\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    for name, shape in leaves.items():
        params = [l for l in lines
                  if re.search(rf"cache__{name}__\S* = {shape}", l)
                  and " parameter(" in l]
        assert len(params) == 1 and "{3,2,1,0" in params[0], (name, params)
    if prog == "decode_block":
        assert "flash_decode_ring_sink" in text \
            and "flash_decode_attention" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


# ---- the two leaves of the Keye-VL-2.0 block (PR 46) -------------------------


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_keye_cache_leaves_are_never_copied_whole(prog, topo, one_chip,
                                                  experts_on_chip):
    """A token's K and V heads in one 2 KB row and the indexer's keys two to
    a row: each leaf stays row-major as it is resident and no instruction
    copies one (a chunk's masked walk holds the blocks it reads to the rows'
    own order: left free, the compiler re-laid the K/V rows a head's keys
    side by side, a copy of the whole leaf, and the chunk did not fit); the
    decode block gathers the chosen rows (ONE gather of 16,384 rows a
    layer) and runs the sixteen held experts as the pipelined pass, the
    chunk as the grouped kernel; the programs' temporaries leave the
    12.75 GB resident room on the chip."""
    compiled = _cell_program(topo, prog, "keye-vl-2.0-ep8-l12")
    text = compiled.as_text()
    _assert_expert_orders(text, prog, pipelined=True)
    lines = text.splitlines()
    leaves = {"kv": (r"bf16\[12,8,49152,8,128\]", "{4,3,2,1,0"),
              "ki": (r"bf16\[12,8,24576,128\]", "{3,2,1,0")}
    shapes = "|".join(shape for shape, _ in leaves.values())
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= (?:{shapes})\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    for name, (shape, order) in leaves.items():
        params = [l for l in lines
                  if re.search(rf"cache__{name}__\S* = {shape}", l)
                  and " parameter(" in l]
        assert len(params) == 1 and order in params[0], (name, params)
    gathers = [l for l in lines
               if re.search(r"= bf16\[8,2048,8,128\]\S* gather\(", l)]
    assert (len(gathers) == 1) == (prog == "decode_block"), gathers
    mem = compiled.memory_analysis()
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 12.75
    assert mem.temp_size_in_bytes < 0.8e9  # 0.23 a block, 0.61 a chunk


@pytest.mark.parametrize("prog", ["blocks", "prefill_chunk"])
def test_sdar_round_of_blocks_and_chunk_leave_the_cache_in_place(
        prog, topo, one_chip, experts_on_chip, monkeypatch):
    """The SDAR cell's programs at its own size (PR 62: 32 slots x 12,288
    beside 2.43 GB of weights). The round of blocks: a denoise forward's
    ``block_length`` rows a slot ride beside the query heads through the
    stacked flash-decode kernel on the ``k``/``v`` leaves where they lie (one
    call a layer in the loop's body, 128 query rows a slot, and one in the
    fused forward that starts a block and commits the one before it, 256:
    ISSUE 66), the sixteen held experts through the pipelined pass at 128
    rows and through the grouped kernel at the fused forward's 256, the loop
    over the steps a ``while`` with the cache in its carry and no copy of a
    leaf. The chunk:
    four K/V heads a token are half a register tile, and left free the
    chunk's contractions re-laid both leaves with the tokens along the lanes
    (two copies of 4.5 GB: the chunk did not fit); ``kv_cache.cache_write``
    holds them row-major. The programs' temporaries leave the 12.09 GB
    resident room on the chip."""
    from picotron_tpu.inference import kv_cache

    monkeypatch.setattr(kv_cache, "on_tpu", lambda: True)
    compiled = _cell_program(topo, prog, "sdar-30b-a3b-ep8-l12")
    text = compiled.as_text()
    _assert_expert_orders(text, prog, pipelined=True, wide=prog == "blocks")
    lines = text.splitlines()
    kv = r"bf16\[12,32,12288,4,128\]"
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= {kv}\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    for leaf in ("k", "v"):
        params = [l for l in lines
                  if re.search(rf"cache__{leaf}__\S* = {kv}", l)
                  and " parameter(" in l]
        assert len(params) == 1 and "{4,3,2,1,0" in params[0], params
    kernels = [l for l in lines
               if re.search(r"%flash_decode_attention\S* = ", l)]
    assert len(kernels) == (2 if prog == "blocks" else 0), kernels
    # 32 slots' 4 x 32 query rows of 128 lanes (a block's forward) and
    # 4 x 64 (the fused forward's two blocks), the leaves whole
    assert all("bf16[12,32,49152,128]" in l for l in kernels)
    assert sorted(re.search(r"= bf16\[32,(\d+),128\]", l).group(1)
                  for l in kernels) == ["128", "256"][:len(kernels)]
    mem = compiled.memory_analysis()
    assert round(mem.argument_size_in_bytes / 1e9, 2) == 12.09
    assert mem.temp_size_in_bytes < (0.4e9 if prog == "blocks" else 0.9e9)

