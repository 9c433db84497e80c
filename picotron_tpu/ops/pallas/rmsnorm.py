"""RMSNorm as a Pallas TPU kernel (fwd + custom-VJP bwd).

TPU-native equivalent of the reference's TritonRMSNorm (picotron/model.py:38-64,
layer_norm_fn from the flash-attn package). Numerics match the pure formulation
in ops/rmsnorm.py (the reference's LlamaRMSNorm, model.py:66-85): variance in
float32, ``x * rsqrt(var + eps)`` cast to the input dtype, scaled by weight.

Rows (B*S flattened) stream through a 1-D grid; the weight gradient
accumulates across grid steps into a single [1, H] output block (TPU grid
iterations over the same output block run sequentially, so the accumulation
is race-free).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pick_block(rows: int, h: int, itemsize: int) -> int:
    """Row-block sized so one block is ~512 KB: with Pallas double-buffering
    and the kernel's fp32 temporaries this keeps VMEM well under the 16 MB
    budget at any hidden size. Either the whole array, or a power of two of
    at least 8 rows (whole sublane groups: Mosaic refuses a smaller block
    that is not the whole array). Where none of those divides the rows the
    answer does not either, and ``_pad_rows`` makes the rows up to it."""
    want = max(8, (512 * 1024) // max(h * itemsize, 1))
    # a power of two, so that halving down to a divisor of the rows keeps
    # whole sublane groups (a width of 5,120 asked for 51 rows, and 64 rows
    # went down to blocks of one)
    want = 1 << (want.bit_length() - 1)
    b = min(want, rows)
    while rows % b and b > 8:
        b //= 2
    return want if rows % b else b


def _pad_rows(x2d, br: int):
    """``x2d`` with zero rows behind it up to a whole number of blocks (a
    zero row's norm is zero, and it adds nothing to the weight's
    gradient)."""
    pad = -x2d.shape[0] % br
    return jnp.pad(x2d, ((0, pad), (0, 0))) if pad else x2d


def _fwd_kernel(x_ref, w_ref, y_ref, *, eps):
    x32 = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = (x32 * jax.lax.rsqrt(var + eps)).astype(y_ref.dtype)
    y_ref[:] = normed * w_ref[0][None, :].astype(y_ref.dtype)


def _bwd_kernel(x_ref, w_ref, dy_ref, dx_ref, dw_ref, *, eps):
    @pl.when(pl.program_id(0) == 0)
    def _():
        dw_ref[:] = jnp.zeros_like(dw_ref)

    x32 = x_ref[:].astype(jnp.float32)
    dy32 = dy_ref[:].astype(jnp.float32)
    w32 = w_ref[0][None, :].astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    xhat = x32 * r
    dxhat = dy32 * w32
    dx = r * (dxhat - xhat * jnp.mean(dxhat * xhat, axis=-1, keepdims=True))
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dw_ref[:] = dw_ref[:] + jnp.sum(dy32 * xhat, axis=0, keepdims=True)


def _run_fwd(x2d, w, eps):
    rows, h = x2d.shape
    br = _pick_block(rows, h, x2d.dtype.itemsize)
    x2d = _pad_rows(x2d, br)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        name="rmsnorm_fwd",
        grid=(x2d.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
    )(x2d, w)[:rows]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms_norm_2d(x2d, w, eps):
    return _run_fwd(x2d, w, eps)


def _fwd_rule(x2d, w, eps):
    return _run_fwd(x2d, w, eps), (x2d, w)


def _bwd_rule(eps, res, dy):
    x2d, w = res
    rows, h = x2d.shape
    br = _pick_block(rows, h, x2d.dtype.itemsize)
    x2d, dy = _pad_rows(x2d, br), _pad_rows(dy, br)
    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        name="rmsnorm_bwd",
        grid=(x2d.shape[0] // br,),
        in_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((br, h), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((br, h), lambda i: (i, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
            jax.ShapeDtypeStruct((1, h), jnp.float32),
        ],
    )(x2d, w, dy)
    return dx[:rows], dw.astype(w.dtype)  # [1, H], the primal w's own shape


_rms_norm_2d.defvjp(_fwd_rule, _bwd_rule)


def rms_norm_pallas(x, weight, eps: float = 1e-5):
    """x: [..., H]; weight: [H]. Same numerics as ops.rmsnorm.rms_norm."""
    shape = x.shape
    h = shape[-1]
    out = _rms_norm_2d(x.reshape(-1, h), weight.reshape(1, h), float(eps))
    return out.reshape(shape)
