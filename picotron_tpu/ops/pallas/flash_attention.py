"""Flash attention as a Pallas TPU kernel (fwd + custom-VJP bwd).

TPU-native equivalent of the reference's flash-attn CUDA dependency
(reference picotron/model.py:7,32-36,151-153; pinned flash-attn==2.5.0).
Same asymptotics as FlashAttention-2: O(S) memory (never materializes the
[S, S] score matrix in HBM), online softmax in fp32, log-sum-exp saved for
the backward, which re-derives P per block.

Three data layouts, and the paired form of the default at heads of 64, share
the same kernel bodies (``model.flash_layout``):

- "folded" (default, battle-tested): the model's [B, S, H, D] is folded to
  [B*H, S, D] around the pallas_call; the grid walks (batch*head, q-block).
  The fold is a transpose+reshape copy of every operand per call, in HBM,
  outside the kernels.
- "bshd" (interpret-mode only — REJECTED on hardware): the kernels consume
  [B, S, H, D] directly — grid (batch, head, q-block), the head dimension
  squeezed out by a size-None BlockSpec entry — avoiding the fold's
  transpose copies. On a v5e chip Mosaic refuses to
  lower it — the last two block dims must be (8k, 128m) or span the whole
  axis, and in [B, S, H, D] the head axis is second-to-last, so a
  squeezed head block is structurally un-lowerable regardless of D. The
  only hardware paths are (a) this folded layout or (b) the "merged"
  layout below. "folded" stays the production default; bshd remains as
  the interpret-mode record of the experiment.
- "merged" (head_dim % 128 == 0 geometries, e.g. Llama-2-7B's D=128): the
  [B, S, H, D] operands are viewed as [B, S, H*D] — a free reshape, minor
  dims merge — and the head grid axis selects a D-wide LANE-aligned slice
  of the last dim, which Mosaic accepts. Same zero-transpose-copy win the
  bshd experiment wanted, within the tiling rules.
- "paired" (heads of 64, an even number of them; not a ``flash_layout``
  value: the training layer stack takes it where ``model.flash_layout`` is
  the default, ``llama.flash_heads_per_row``): merged's idea below 128
  lanes. A block is the 128 lanes of heads 2p and 2p+1; the grid walks
  (batch, head pair, tile, head of the pair), the pair's two programs one
  after the other on the same blocks. A program zeroes the other head's
  lanes of ONE operand of each contraction over D (q forward; k and v
  backward), so the matmul over 128 lanes is its own head's, at the MXU
  depth a head of 64 padded to the array had; what comes out 128 lanes wide
  (p @ v, dV, dK) is right in its own lanes and is merged into the pair's
  block; dQ gathers both heads in one [S, 128] float32 scratch (the masked k
  adds zeros to the other head's lanes). Same tiles, mask, scale fold and
  five-matmul backward, bit for bit the folded result. The forward's lse
  leaves compact, [B, H, S] rows along lanes (1/128 of the folded layout's).

What a call costs around the kernels at [3, 2048, 32, 64] (v5e, PERF.md
PR 48): folded, twelve relayout copies of a q-sized array a layer (q, k, v
in, the output back, again for the ``remat: full`` recompute, dO in, dQ, dK,
dV back: 0.07-0.14 ms each), the lse's [96, 2048, 128] float32 written
twice and sliced once (0.22 ms), RoPE through the [.., H, D] view
(sequence-minor, 0.39 ms), delta 0.05; 1.64 ms a layer in all. Paired: none
of the copies, a [3, 32, 2048] lse, RoPE by ``ops/pallas/rope.py`` on the rows
as the projections wrote them (0.40 ms), a forward kernel 0.06 ms slower a
call: the cell's step is 1.2 ms a layer shorter.

K/V for one head live whole in VMEM (S*D*2B ~ 1 MB at S=8192, D=64)
while scores exist only as a [block_q, block_k] VMEM tile. The forward's
per-row LSE is materialized with a broadcast 128-lane minor dim
([BH, S, 128] / [B, S, H, 128]; the paired form's is compact) — Mosaic
requires the last two block dims be
(8k, 128m), so a lane-less layout can't be tiled per-q-block (the in-tree TPU
flash kernel uses the same trick); the residual and the backward's operands
are compact. GQA repetition happens in the model before the call (as the
reference repeats before its kernel, model.py:141-142).

Causality: whole key-blocks strictly above the diagonal are skipped (the
fori_loop bounds), every tile walked gets the iota mask.

What a tile pair costs, as the compiler schedules it for a v5e (the final
bundles of a described-chip compile; one bundle ~0.73 ns on the chip, PERF.md
PR 33). The [512, 512] float32 score tile is 256 vector registers of a file
of 64, so every stage streams through VMEM and the loop body is bound by its
MXU pushes first (a 16-row bf16 push a ~15 cycles; heads of 64 half-fill the
array, so D 64 and D 128 cost the same) and by the single vector-store slot a
bundle second, not by vector ALU work: the forward's pair is ~1,350 bundles
for ~930 of MXU time, the backward's ~2,500 for ~2,330. So:

- the mask stays on every walked tile. Masking only the tiles the diagonal
  crosses needs a second loop, and handing the carried accumulators from one
  loop to the next costs more (~600 bundles a query tile) than the mask (~40
  a pair): measured 1.34 ms a forward call against 1.20 at S 2048;
- the scale leaves the score tile: a power of two (1/8 at D 64) is folded
  into q (k in the backward), exact in bf16; in the backward ds * scale is
  linear and is applied to the float32 [rows, D] accumulators at the end;
- the forward's row sum stays a [bq, 128] lane-partial sum (vector adds)
  until the walk ends, one cross-lane reduction a query tile;
- the backward is ONE kernel: the score tile is built once a pair, keys along
  rows, and feeds dV, dK and dQ (five matmuls a pair where a dQ kernel of its
  own made it seven; no tile transpose), with delta = rowsum(dO * O)
  computed once a call outside it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANE = 128  # minor-dim width for the broadcast LSE layout

# 512 x 512 measured fastest on v5e at both training shapes (PR 33, ms a
# call forward / backward at [3, 2048, 32, 64]: 512x512 1.20 / 2.07, 1024x512
# 1.36 / 2.31, 512x1024 1.28 / 2.44, 1024x1024 1.32 / 2.32, 256x512 1.33 /
# 2.62, 512x256 1.79 / 2.44; at [1, 4096, 16, 128] 0.667 / 1.175 against
# 0.651-1.02 / 1.23-1.53): a loop iteration carries a fixed cost (the
# accumulators through VMEM, pipeline fill) that a bigger tile amortizes,
# and past 512 the causal walk wastes more of its diagonal tiles.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _pick_block(seq: int, want: int) -> int:
    b = min(want, seq)
    while seq % b:
        b //= 2
    return max(b, 1)


def causal_kv_blocks(nk, q_hi, block_k):
    """Leading ``block_k``-row KV blocks that intersect key positions
    ``<= q_hi`` — the causal block-skip bound. Shared machinery: the
    training flash forward/backward kernels bound their key walk with it
    (``q_hi`` = the q-tile's last row position), and the decode/chunked-
    prefill kernel (ops/pallas/decode_attention.py) reuses it with
    ``q_hi`` additionally clipped to the slot's live length, so early
    prefill chunks and short sequences alike skip whole blocks instead of
    masking them."""
    return jnp.minimum(nk, (q_hi + block_k) // block_k)


def _scale_folds(scale: float) -> bool:
    """A power of two (1/8 at heads of 64): multiplying one matmul operand
    by it beforehand is exact in bf16 and float32 alike, so the [bq, bk]
    score tile needs no multiply."""
    return math.frexp(scale)[0] == 0.5


def _causal_band(s, q0, k0, q_axis=0):
    """Scores above the diagonal to NEG_INF. ``s`` holds query rows from
    ``q0`` along ``q_axis`` and key rows from ``k0`` along the other."""
    qpos = (q0 - k0) + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kpos = lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _dot_nt(a, b):
    """a @ b.T: [m, d] x [n, d] -> [m, n] in float32."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _dot(a, b):
    """a @ b: [m, n] x [n, d] -> [m, d] in float32."""
    return lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _dot_tn(a, b):
    """a.T @ b: [n, m] x [n, d] -> [m, d] in float32 (the MXU takes the
    transposed operand as it is pushed: no transpose of ``a``)."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


PAIR = 2  # heads of LANE / 2 to a lane row in the paired form


def _own_lanes(blk_axis):
    """(this program's head of a paired row, [1, LANE] mask of its lanes):
    the grid axis after the tile axis runs the pair's heads."""
    sub = pl.program_id(blk_axis + 1)
    half = lax.broadcasted_iota(jnp.int32, (1, LANE), 1) // (LANE // PAIR)
    return sub, half == sub


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_q,
                block_k, causal, blk_axis=1, paired=False):
    # Matmul inputs stay in their native dtype (bf16 in training) with fp32
    # accumulation via preferred_element_type — fp32 MXU issue rate is 1/8
    # of bf16 on TPU, so casting q/k/v up would throttle the whole kernel.
    # Softmax state (m, l, acc) is fp32. blk_axis: which grid axis walks the
    # q-blocks (1 = folded (BH, nq) grid, 2 = bshd (B, H, nq) grid).
    # paired: the tiles are 128 lanes of two heads of 64 and this program
    # runs one of them. Its q loses the other head's lanes, so q @ k.T
    # contracts over its own (the MXU pass is as deep as a head of 64
    # padded to the array was); p @ v comes out right in its own lanes, and
    # only those are stored.
    qi = pl.program_id(blk_axis)
    fold = _scale_folds(scale)
    q = q_ref[0]  # [bq, D]
    if paired:
        sub, own = _own_lanes(blk_axis)
        q = jnp.where(own, q, jnp.zeros_like(q))
    if fold:
        q = q * scale  # exact; once a query tile, not once a score
    nk = k_ref.shape[1] // block_k
    if causal:
        # key blocks that intersect rows <= this q block's last row
        nk = causal_kv_blocks(nk, (qi + 1) * block_q - 1, block_k)
    # The row sum stays a [bq, LANE] partial sum over lane-wide column
    # blocks (plain vector adds) until the walk ends: one cross-lane
    # reduction a query tile, not one a tile pair.
    cols = block_k // LANE if block_k % LANE == 0 else 0

    def body(j, carry):
        acc, m, l = carry
        k0 = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(k0, block_k), :]
        v = v_ref[0, pl.ds(k0, block_k), :]
        s = _dot_nt(q, k)
        if not fold:
            s = s * scale
        if causal:
            s = _causal_band(s, qi * block_q, k0)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        if cols:
            psum = p[:, :LANE]
            for c in range(1, cols):
                psum = psum + p[:, c * LANE:(c + 1) * LANE]
        else:
            psum = jnp.sum(p, axis=1, keepdims=True)
        l = l * alpha + psum
        acc = acc * alpha + _dot(p.astype(v.dtype), v)
        return acc, m_new, l

    bq, d = q.shape
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, LANE if cols else 1), jnp.float32)
    acc, m, l = lax.fori_loop(0, nk, body, (acc0, m0, l0))
    l = jnp.sum(l, axis=1, keepdims=True)
    out = (acc / l).astype(o_ref.dtype)
    lse = jnp.broadcast_to(m + jnp.log(l), (bq, LANE))
    if not paired:
        o_ref[0] = out
        lse_ref[0] = lse
        return
    # the pair's two programs share the out block: each its own lanes
    o_ref[0] = jnp.where(own, out, o_ref[0])
    # The lse leaves compact, query rows along lanes (what the backward
    # reads): at 128 lanes a head a token it is four times q's bytes, written
    # by every call and read back through a slice. Row r of a stretch of 128
    # moves to lane r: a select on the diagonal and a sum down the sublanes.
    g = min(bq, LANE)
    diag = (lax.broadcasted_iota(jnp.int32, (g, LANE), 0)
            == lax.broadcasted_iota(jnp.int32, (g, LANE), 1))
    rows = jnp.where(diag, lse.reshape(bq // g, g, LANE), 0.0)
    lse_ref[0, sub, qi] = jnp.sum(rows, axis=1)[:, :g]


def _fwd(q, k, v, scale, causal, block_q, block_k, layout="folded"):
    """folded: q [BH,Sq,D] -> (out [BH,Sq,D], lse [BH,Sq,LANE]).
    bshd/merged: q [B,Sq,H,D] -> (out [B,Sq,H,D], lse [B,Sq,H,LANE]);
    paired: the same with lse [B,Sq,H,1].
    LSE is the broadcast-lane fp32 layout (paired: compact). Sq and Sk may
    differ
    (ring-attention half blocks); causal requires Sq == Sk (aligned
    positions)."""
    sq, sk = q.shape[1], k.shape[1]
    d = q.shape[-1]
    assert not causal or sq == sk
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    post = lambda out, lse: (out, lse)
    if layout in ("merged", "paired"):
        # [B, S, H, D] viewed as [B, S, H*D] (free: minor dims merge), the
        # head index a grid axis selecting a LANE-aligned slice: D wide
        # where D % 128 == 0 (merged), and at D 64 the 128 lanes of heads
        # 2p and 2p+1 (paired), which a last grid axis of 2 runs one after
        # the other on the same blocks. The kernels consume the model
        # layout with ZERO transpose copies.
        b, h = q.shape[0], q.shape[2]
        per = PAIR if layout == "paired" else 1  # heads a block
        w = d * per
        q, k, v = (x.reshape(x.shape[0], x.shape[1], h * d)
                   for x in (q, k, v))
        grid = (b, h // per, sq // bq) + ((per,) if per > 1 else ())
        blk_axis = 2
        in_specs = [
            pl.BlockSpec((1, bq, w), lambda b_, hh, i, *_: (b_, i, hh)),
            pl.BlockSpec((1, sk, w), lambda b_, hh, i, *_: (b_, 0, hh)),
            pl.BlockSpec((1, sk, w), lambda b_, hh, i, *_: (b_, 0, hh)),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, w), lambda b_, hh, i, *_: (b_, i, hh)),
            pl.BlockSpec((1, bq, LANE), lambda b_, hh, i, *_: (b_, i, hh)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b, sq, h * d), q.dtype),
            jax.ShapeDtypeStruct((b, sq, h * LANE), jnp.float32),
        ]
        post = lambda out, lse: (out.reshape(b, sq, h, d),
                                 lse.reshape(b, sq, h, LANE))
        if per > 1:
            # the lse compact, [B, H, nq, bq / 128, 128]: a pair's block
            # stays in VMEM over its query tiles, each program writes its own
            g = min(bq, LANE)
            tile = (sq // bq, bq // g, g)
            out_specs[1] = pl.BlockSpec(
                (1, per) + tile, lambda b_, hh, *_: (b_, hh, 0, 0, 0))
            out_shape[1] = jax.ShapeDtypeStruct((b, h) + tile, jnp.float32)
            post = lambda out, lse: (
                out.reshape(b, sq, h, d),
                lse.reshape(b, h, sq).transpose(0, 2, 1)[..., None])
    elif layout == "folded":
        bh = q.shape[0]
        grid = (bh, sq // bq)
        blk_axis = 1
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b, 0, 0)),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LANE), lambda b, i: (b, i, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, LANE), jnp.float32),
        ]
    else:
        b, h = q.shape[0], q.shape[2]
        grid = (b, h, sq // bq)
        blk_axis = 2
        in_specs = [
            pl.BlockSpec((1, bq, None, d), lambda b, hh, i: (b, i, hh, 0)),
            pl.BlockSpec((1, sk, None, d), lambda b, hh, i: (b, 0, hh, 0)),
            pl.BlockSpec((1, sk, None, d), lambda b, hh, i: (b, 0, hh, 0)),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, None, d), lambda b, hh, i: (b, i, hh, 0)),
            pl.BlockSpec((1, bq, None, LANE), lambda b, hh, i: (b, i, hh, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((b, sq, h, d), q.dtype),
            jax.ShapeDtypeStruct((b, sq, h, LANE), jnp.float32),
        ]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, block_q=bq, block_k=bk,
                          causal=causal, blk_axis=blk_axis,
                          paired=layout == "paired"),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        name="flash_fwd",
        out_shape=out_shape,
    )(q, k, v)
    return post(out, lse)


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #
#
# One kernel a call, named for the dK/dV it walks by: the grid walks key tiles,
# each program loops over the query tiles that see its keys, and the score
# tile is built once a pair and feeds all three gradients (five matmuls a
# pair; a dQ kernel of its own rebuilt s, p and dp: seven). The tile lies in
# the orientation dK and dV want: keys along rows, queries along lanes
# ([bk, bq] = k @ q.T), so dV += p.T @ dO and dK += ds.T @ q are plain
# row-by-column matmuls of the tile as it lies; dQ += ds @ k contracts the
# tile's rows, which the MXU takes as a transposed operand push with no
# transpose of the tile. dQ gathers over the key tiles in a float32 [Sq, D]
# scratch and leaves with the head's last key tile (the key-tile grid axis is
# declared sequential: ``dimension_semantics``). lse and the softmax-backward row term delta = rowsum(dO * O)
# depend on the query row alone: ``_bwd`` computes delta once a call and both
# enter as rows along lanes, [nq, bq], still compact. ds = p * (dp - delta) *
# scale is linear in the scale, so the scale leaves the [bk, bq] tile for the
# float32 [rows, D] accumulators at the end.


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, *, scale, block_q, block_k,
                causal, blk_axis=1, paired=False):
    kj = pl.program_id(blk_axis)
    fold = _scale_folds(scale)
    k = k_ref[0]  # [bk, D]
    v = v_ref[0]
    first, last = kj == 0, kj == pl.num_programs(blk_axis) - 1
    if paired:
        # this program's head of the pair (``_fwd_kernel``): k and v lose
        # the other head's lanes, so k @ q.T and v @ dO.T contract over its
        # own and dst.T @ k adds zeros to the other head's lanes of dq_acc;
        # dK and dV come out right in its own lanes, which are stored.
        sub, own = _own_lanes(blk_axis)
        k = jnp.where(own, k, jnp.zeros_like(k))
        v = jnp.where(own, v, jnp.zeros_like(v))
        first, last = first & (sub == 0), last & (sub == PAIR - 1)
    ks = k * scale if fold else k  # exact; once a key tile
    nq = q_ref.shape[1] // block_q
    # first q block that can see this k block
    i0 = (kj * block_k) // block_q if causal else 0

    @pl.when(first)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def body(i, carry):
        dk, dv = carry
        q0 = pl.multiple_of(i * block_q, block_q)
        q = q_ref[0, pl.ds(q0, block_q), :]
        do = do_ref[0, pl.ds(q0, block_q), :]
        lse = lse_ref[0, pl.ds(i, 1), :]  # [1, bq]
        delta = delta_ref[0, pl.ds(i, 1), :]
        st = _dot_nt(ks, q)  # [bk, bq]
        if not fold:
            st = st * scale
        if causal:
            st = _causal_band(st, q0, kj * block_k, q_axis=1)
        pt = jnp.exp(st - lse)
        dv = dv + _dot(pt.astype(do.dtype), do)
        dst = (pt * (_dot_nt(v, do) - delta)).astype(q.dtype)
        dk = dk + _dot(dst, q)
        dq_acc[pl.ds(q0, block_q), :] += _dot_tn(dst, k)
        return dk, dv

    z = jnp.zeros(k.shape, jnp.float32)
    dk, dv = lax.fori_loop(i0, nq, body, (z, z))
    dk = (dk * scale).astype(dk_ref.dtype)
    dv = dv.astype(dv_ref.dtype)
    if paired:
        dk = jnp.where(own, dk, dk_ref[0])
        dv = jnp.where(own, dv, dv_ref[0])
    dk_ref[0] = dk
    dv_ref[0] = dv

    @pl.when(last)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd(scale, causal, block_q, block_k, layout, res, dout):
    q, k, v, out, lse_c = res
    sq, sk = q.shape[1], k.shape[1]
    d = q.shape[-1]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    nq = sq // bq
    # Residuals carry the compact (lane-less) LSE ([BH, Sq] / [B, Sq, H]: the
    # forward's broadcast LANE layout is 128x larger, which matters when a
    # remat policy saves it). delta is computed here, once a call, in the
    # same layout; both reach the kernel as [.., nq, bq] rows (``rows``).
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    # q_spec: a head's whole q / dO / dQ; k_spec: one key tile of k / v /
    # dK / dV. Operand order is layout-independent; only these vary.
    per = PAIR if layout == "paired" else 1  # heads a block
    if layout == "folded":
        bh = q.shape[0]
        grid, blk_axis = (bh, sk // bk), 1
        rows = lambda x: x.reshape(bh, nq, bq)
        row_spec = pl.BlockSpec((1, nq, bq), lambda b, j: (b, 0, 0))
        q_spec = pl.BlockSpec((1, sq, d), lambda b, j: (b, 0, 0))
        k_spec = pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0))
        shape = lambda s: (bh, s, d)
    else:
        b, h = q.shape[0], q.shape[2]
        grid, blk_axis = (b, h // per, sk // bk), 2
        rows = lambda x: x.transpose(0, 2, 1).reshape(b, h, nq, bq)
        row_spec = pl.BlockSpec((1, None, nq, bq),
                                lambda b_, hh, j: (b_, hh, 0, 0))
        if layout in ("merged", "paired"):
            w = d * per
            q, dout = (x.reshape(b, sq, h * d) for x in (q, dout))
            k, v = (x.reshape(b, sk, h * d) for x in (k, v))
            q_spec = pl.BlockSpec((1, sq, w),
                                  lambda b_, hh, j, *_: (b_, 0, hh))
            k_spec = pl.BlockSpec((1, bk, w),
                                  lambda b_, hh, j, *_: (b_, j, hh))
            shape = lambda s: (b, s, h * d)
            if per > 1:  # the pair's heads one after the other, innermost
                grid += (per,)
                row_spec = pl.BlockSpec(
                    (1, None, nq, bq),
                    lambda b_, hh, j, sub: (b_, hh * per + sub, 0, 0))
        else:
            q_spec = pl.BlockSpec((1, sq, None, d),
                                  lambda b_, hh, j: (b_, 0, hh, 0))
            k_spec = pl.BlockSpec((1, bk, None, d),
                                  lambda b_, hh, j: (b_, j, hh, 0))
            shape = lambda s: (b, s, h, d)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, block_q=bq, block_k=bk,
                          causal=causal, blk_axis=blk_axis,
                          paired=layout == "paired"),
        grid=grid,
        in_specs=[q_spec, k_spec, k_spec, q_spec, row_spec, row_spec],
        out_specs=[q_spec, k_spec, k_spec],
        name="flash_bwd_dkv",
        out_shape=[jax.ShapeDtypeStruct(shape(sq), q.dtype),
                   jax.ShapeDtypeStruct(shape(sk), k.dtype),
                   jax.ShapeDtypeStruct(shape(sk), v.dtype)],
        scratch_shapes=[pltpu.VMEM((sq, d * per), jnp.float32)],
        # dq_acc gathers over a head's key tiles (a pair's, with its two
        # heads): those axes run in order, innermost, whatever a later
        # compiler does with the others
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * blk_axis
            + ("arbitrary",) * (len(grid) - blk_axis)),
    )(q, k, v, dout, rows(lse_c), rows(delta))
    if layout in ("merged", "paired"):  # back to [B, S, H, D] (free)
        dq = dq.reshape(b, sq, h, d)
        dk = dk.reshape(b, sk, h, d)
        dv = dv.reshape(b, sk, h, d)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# public API
# --------------------------------------------------------------------------- #


def _check_layout(layout: str, d: int | None = None,
                  h: int | None = None) -> None:
    if layout not in ("folded", "bshd", "merged", "paired"):
        raise ValueError(
            f"unknown flash layout {layout!r} (folded|bshd|merged|paired)")
    if layout == "merged" and d is not None and d % LANE:
        raise ValueError(
            f"flash layout 'merged' needs head_dim % {LANE} == 0 (the head "
            f"slice must be a whole lane tile); got head_dim={d}")
    if layout == "paired" and (2 * d != LANE or h % 2):
        raise ValueError(
            f"flash layout 'paired' needs heads of {LANE // 2}, an even "
            f"number of them (two to a lane row); got {h} of {d}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, scale, causal, block_q, block_k, layout):
    out, _ = _fwd(q, k, v, scale, causal, block_q, block_k, layout)
    return out


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, layout):
    out, lse = _fwd(q, k, v, scale, causal, block_q, block_k, layout)
    # checkpoint_name lets a selective remat policy (llama.layers_forward,
    # remat="save_attn") keep out+lse across the backward, so rematerialized
    # backward passes skip the flash forward kernel entirely.
    out = checkpoint_name(out, "flash_out")
    lse_c = checkpoint_name(lse[..., 0], "flash_lse")
    return out, (q, k, v, out, lse_c)


_flash_core.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, scale: float | None = None, causal: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    layout: str = "folded"):
    """q, k, v: [B, S, H, D] with equal head counts. Returns [B, S, H, D].
    layout="merged" (head_dim % 128 == 0 only) and layout="bshd"
    (interpret-mode only; Mosaic rejects it on hardware) run the kernels on
    the model layout directly with no fold copies; "folded" is the
    always-available default."""
    b, s, h, d = q.shape
    _check_layout(layout, d, h)
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if layout != "folded":
        return _flash_core(q, k, v, float(scale), causal, block_q, block_k,
                           layout)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    out = _flash_core(fold(q), fold(k), fold(v), float(scale), causal,
                      block_q, block_k, "folded")
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def flash_block_grads(q, k, v, out, lse, dout, scale: float,
                      causal: bool = True,
                      block_q: int | None = None,
                      block_k: int | None = None,
                      layout: str = "folded"):
    """Gradients of one attention block given an externally-merged (global)
    out/lse — the ring-attention backward building block (the ring re-derives
    each block's true share of the global softmax as exp(s - lse_global),
    reference context_parallel.py:112-155). q/out/dout are [B, Sq, H, D],
    k/v are [B, Sk, H, D] (Sq != Sk allowed for ring half-blocks, non-causal
    only); lse is [B, Sq, H] fp32. Returns (dq, dk, dv)."""
    b, sq, h, d = q.shape
    _check_layout(layout, d, h)
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    if layout != "folded":
        return _bwd(scale, causal, block_q, block_k, layout,
                    (q, k, v, out, lse), dout)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
    lse_c = lse.transpose(0, 2, 1).reshape(b * h, sq)
    dq, dk, dv = _bwd(scale, causal, block_q, block_k, "folded",
                      (fold(q), fold(k), fold(v), fold(out), lse_c),
                      fold(dout))
    unfold = lambda x: x.reshape(b, h, x.shape[1], d).transpose(0, 2, 1, 3)
    return unfold(dq), unfold(dk), unfold(dv)


def flash_attention_with_lse(q, k, v, scale: float | None = None,
                             causal: bool = True,
                             block_q: int | None = None,
                             block_k: int | None = None,
                             layout: str = "folded"):
    """Forward-only variant returning (out [B,Sq,H,D], lse [B,Sq,H]) — the
    building block for ring attention's LSE merge. Sq != Sk allowed
    (non-causal only)."""
    b, s, h, d = q.shape
    _check_layout(layout, d, h)
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if layout != "folded":
        out, lse = _fwd(q, k, v, float(scale), causal, block_q, block_k,
                        layout)
        return out, lse[..., 0]
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
    out, lse = _fwd(fold(q), fold(k), fold(v), float(scale), causal,
                    block_q, block_k, "folded")
    return (out.reshape(b, h, s, d).transpose(0, 2, 1, 3),
            lse[:, :, 0].reshape(b, h, s).transpose(0, 2, 1))
