"""Fused dequant matmul over per-channel int8 weights.

The weight-side counterpart of the int8 KV cache (inference/kv_cache.py):
weights are stored as int8 values with ONE fp32 scale per output channel
(``quantize_weight`` — absmax over the contraction axis, so quantization
error never crosses a channel), and the matmul consumes that storage
directly. A Llama-2-7B checkpoint's matmul weights land on device at
~half the bf16 bytes (1 byte/element + 4/in_features for the scales ≈
50.1% of bf16), which is what opens the 7B-class serving scenario on a
small slice (ROADMAP item 3).

Two implementations behind one entry point, ``quant_matmul(x, q, s)``:

- **Pallas kernel** (TPU, or ``interpret=True`` for the CPU parity
  suite): a ``(M//bm, N//bn)`` grid; each instance walks the contraction
  in ``block_k`` tiles pulled from the int8 VMEM block, casts the tile to
  the activation dtype IN REGISTERS (int8 values are at most ±127 —
  exactly representable in bf16, so the cast is lossless and the MXU
  runs at full bf16 rate), accumulates in fp32 via
  ``preferred_element_type``, and applies the per-output-channel scale
  ONCE to the fp32 accumulator in the epilogue. Per-channel scales
  commute with the contraction (``x @ (q * s[None, :]) ==
  (x @ q) * s[None, :]`` exactly, in real arithmetic), so scaling the
  epilogue IS the per-channel dequant — fused past the matmul, touching
  [bm, bn] accumulator elements instead of [K, N] weight elements. At no
  point does a dequantized copy of the weight exist anywhere: not in
  HBM, not in VMEM — the widest dequant-adjacent object is the one
  [block_k, bn] int8->bf16 register tile feeding the MXU.
- **XLA fallback** (off-TPU serving / any platform): the same
  scale-after-accumulate ordering as one ``jnp.einsum`` over the int8
  values (cast to the activation dtype) with the scale broadcast applied
  to the fp32 result. Bit-for-bit it differs from the kernel only in
  contraction order; both are allclose to the fake-quant reference
  ``x @ dequantize_weight(q, s)`` (tests/test_quant_weights.py).

``dequantize_weight`` exists for tests and offline tooling ONLY. The
serving path must never call it — tests/test_quant_weights.py enforces
that the same way test_decode_kernel.py pins the KV path: the helper is
monkeypatched to raise and full int8-weight generations still run.

Tiling notes: block sizes follow ``flash_attention._pick_block``
(halve-until-divides, so any K/N tiles exactly — the tiny CPU test
shapes degrade to small blocks, real model dims keep the 512/256
defaults). The M axis (tokens x folded batch) pads to the fp32 sublane
quantum. int8's native (32, 128) VMEM tile means very small K slices
underutilize lanes on real hardware; the shapes this kernel serves
(H >= 2048) never hit that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.ops.pallas.flash_attention import _pick_block
from picotron_tpu.utils import on_tpu

# int8 symmetric range; scales are fp32 so the epilogue multiply never
# double-rounds — the same convention as the int8 KV cache
# (inference/kv_cache.py::INT8_MAX / SCALE_DTYPE).
INT8_MAX = 127.0
SCALE_DTYPE = jnp.float32

DEFAULT_BLOCK_M = 256  # token rows per grid instance (decode: B*S, tiny)
DEFAULT_BLOCK_N = 256  # output channels per grid instance
DEFAULT_BLOCK_K = 512  # contraction tile dequantized in registers per step
_SUBLANE = 8  # fp32 sublane quantum the padded M respects


def is_quant_weight(leaf) -> bool:
    """Whether a parameter leaf is a quantized ``(int8, scales)`` pair —
    the dict form ``{"q": int8 [..., in, out], "s": fp32 [..., out]}`` the
    model's matmul sites dispatch on (models/llama.py::matmul)."""
    return isinstance(leaf, dict) and set(leaf) == {"q", "s"}


def quantize_weight(w) -> dict:
    """Per-output-channel absmax int8 quantization of a matmul weight.

    ``w`` is [..., in_features, out_features] (our (in, out) storage
    layout, optionally layer-stacked); the scale reduces over the
    CONTRACTION axis (-2), one fp32 scale per output channel — so a
    TP-sharded column split carries exactly the global quantization's
    values and scales for its channels (scales shard WITH their
    channels). The STORED scale is the exact divisor the values were
    rounded against (the raw absmax/127 clamped away from zero), so the
    |Δw| <= scale/2 per-element bound holds for every channel including
    denormal-tiny ones; an all-zero channel quantizes to zeros with
    scale 0 — dequantization is exact there (pad rows of uneven-pp
    stacks stay exactly zero)."""
    wf = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-2)
    div = jnp.maximum(amax / INT8_MAX, 1e-12)
    q = jnp.round(wf / div[..., None, :])
    return {"q": jnp.clip(q, -INT8_MAX, INT8_MAX).astype(jnp.int8),
            "s": jnp.where(amax > 0, div, 0.0).astype(SCALE_DTYPE)}


def quantize_weight_host(w: np.ndarray) -> dict:
    """``quantize_weight`` on host numpy — the checkpoint streaming path
    (one layer's fp weight in RAM at a time, int8 out; checkpoint.py)."""
    wf = np.asarray(w, np.float32)
    amax = np.max(np.abs(wf), axis=-2)
    div = np.maximum(amax / INT8_MAX, np.float32(1e-12))
    q = np.round(wf / div[..., None, :])
    return {"q": np.clip(q, -INT8_MAX, INT8_MAX).astype(np.int8),
            "s": np.where(amax > 0, div, 0.0).astype(np.float32)}


def dequantize_weight(q, s, dtype=jnp.float32):
    """Inverse of ``quantize_weight`` — TESTS AND OFFLINE TOOLING ONLY.
    The serving path never materializes this (enforced by monkeypatching
    this helper to raise in tests/test_quant_weights.py, the
    test_decode_kernel.py discipline)."""
    return (jnp.asarray(q).astype(jnp.float32)
            * jnp.asarray(s)[..., None, :]).astype(dtype)


# --------------------------------------------------------------------------- #
# the kernel
# --------------------------------------------------------------------------- #


def _quant_matmul_kernel(x_ref, q_ref, s_ref, o_ref, *, block_k):
    """One (m, n) grid instance: [bm, K] activations against the [K, bn]
    int8 weight block. The contraction walks ``block_k`` tiles: each int8
    tile casts to the activation dtype in registers (lossless — int8
    values are exact in bf16) and feeds the MXU with fp32 accumulation;
    the per-output-channel fp32 scale lands once on the accumulator in
    the epilogue (per-channel scales commute with the contraction, so
    this IS the dequant, fused). No dequantized weight tensor ever
    exists."""
    nk = x_ref.shape[1] // block_k

    def body(j, acc):
        xb = x_ref[:, pl.ds(j * block_k, block_k)]
        wb = q_ref[pl.ds(j * block_k, block_k), :].astype(xb.dtype)
        return acc + lax.dot_general(
            xb, wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc0 = jnp.zeros((x_ref.shape[0], q_ref.shape[1]), jnp.float32)
    acc = lax.fori_loop(0, nk, body, acc0)
    o_ref[:] = (acc * s_ref[0, :][None, :]).astype(o_ref.dtype)


def quant_matmul_pallas(x2, q, s, *, block_m=None, block_n=None,
                        block_k=None, out_dtype=None,
                        interpret: bool = False):
    """The Pallas path: x2 [M, K] @ q [K, N] int8 with s [N] fp32 scales
    -> [M, N] in ``out_dtype`` (default: x2.dtype). M pads to the sublane
    quantum; N/K tile by halve-until-divides blocks."""
    M, K = x2.shape
    N = q.shape[1]
    dt = jnp.dtype(out_dtype or x2.dtype)
    Mp = -(-max(M, 1) // _SUBLANE) * _SUBLANE
    if Mp != M:
        x2 = jnp.pad(x2, ((0, Mp - M), (0, 0)))
    bm = _pick_block(Mp, block_m or DEFAULT_BLOCK_M)
    bn = _pick_block(N, block_n or DEFAULT_BLOCK_N)
    bk = _pick_block(K, block_k or DEFAULT_BLOCK_K)
    kernel = functools.partial(_quant_matmul_kernel, block_k=bk)
    out = pl.pallas_call(
        kernel,
        name="quant_matmul",
        grid=(Mp // bm, N // bn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), dt),
        interpret=interpret,
    )(x2, q, s.reshape(1, N))
    return out[:M]


def quant_matmul_xla(x2, q, s, *, out_dtype=None):
    """The XLA fallback (off-TPU serving and any non-Pallas platform):
    one einsum over the int8 values cast to the activation dtype, fp32
    accumulation, the per-channel scale broadcast onto the fp32 result —
    the kernel's exact ordering minus the K-blocking. Never materializes
    a dequantized weight either: the cast int8 operand IS the matmul
    input."""
    dt = jnp.dtype(out_dtype or x2.dtype)
    acc = jnp.einsum("mk,kn->mn", x2, q.astype(x2.dtype),
                     preferred_element_type=jnp.float32)
    return (acc * s[None, :].astype(jnp.float32)).astype(dt)


def quant_matmul(x, q, s, *, out_dtype=None, impl: str | None = None,
                 interpret: bool = False, block_m=None, block_n=None,
                 block_k=None):
    """``x @ W`` from int8 weights + per-output-channel fp32 scales.

    x: [..., in_features] activations (any leading shape — the model's
    [B, S, H] sites flatten through); q: [in_features, out_features]
    int8; s: [out_features] fp32. Returns [..., out_features] in
    ``out_dtype`` (default: x.dtype).

    ``impl``: "pallas" | "xla" | None (auto: the Pallas kernel on TPU,
    the XLA fallback elsewhere — the same dispatch rule as
    ``inference.attend_impl``'s interpret-mode guard). ``interpret``
    forces the Pallas interpreter (the CPU parity suite)."""
    if q.dtype != jnp.int8:
        raise ValueError(f"quant_matmul weights must be int8, got {q.dtype}")
    if impl is None:
        impl = "pallas" if (on_tpu() or interpret) else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown quant_matmul impl {impl!r} (pallas|xla)")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if impl == "pallas":
        out = quant_matmul_pallas(x2, q, s, block_m=block_m,
                                  block_n=block_n, block_k=block_k,
                                  out_dtype=out_dtype, interpret=interpret)
    else:
        out = quant_matmul_xla(x2, q, s, out_dtype=out_dtype)
    return out.reshape(*lead, q.shape[1])
