"""The gated delta rule with a decay for every key channel (Kimi delta
attention) over a float32 state ``S[h]`` [keys, values]:

    S' = Diag(a_t) S_{t-1}                      a_t = exp(g_t), g_t <= 0
    S_t = S' + b_t k_t (v_t - S'^T k_t)^T       b_t in (0, 2)
    o_t = S_t^T q_t

The decay first, then the READ along ``k_t``, then the write of the
difference from what was there, then the read-out from the new state.
``models/solar_open2.py`` runs it: a prefill chunk as the chunked form
(``kda_scan``), a decode step as the recurrence written out (``kda_step``),
taken on the layer's row of the stacked state leaf (``row=``) as
``ops/ssm.py::ssm_step`` takes its own: on a TPU one Pallas pass over the
row, in place (``ops/pallas/kda_step.py``), elsewhere the step below between
a slice and an update. ``ops/ssm.py``'s recurrence (a scalar
decay a head, a plain rank-one add) cannot express this one: the state is
read before it is written, and the chunked form solves a triangular system.

``g = 0`` and ``b = 0`` leave the state exactly as it is (``exp(0) S + 0``):
how a pad row or a parked slot is kept out of it, as ``dt = 0`` for
``ops/ssm.py``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from picotron_tpu.utils import on_tpu

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
# rows the chunked form solves together: a unit lower-triangular system of
# this order a head, and an exp for every (row, earlier row, key channel)
SUB_CHUNK = 64


def kda_step(q, k, v, g, b, S_in, row=None) -> tuple:
    """One row a sequence: (o [B, 1, heads, values] float32, the new
    state). ``q``/``k`` [B, 1, heads, keys] (normalised by the caller),
    ``v`` [B, 1, heads, values], ``g`` [B, 1, heads, keys] float32 (<= 0),
    ``b`` [B, 1, heads] float32, ``S_in`` [B, heads, keys, values] float32.

    As the compiler runs it, two passes over the state: one reads it along
    ``k`` and along ``q`` (both sums over the decayed ``S'``), one writes
    it. The read-out from the new state is ``S_t^T q = S'^T q + (k . q) u``
    with ``u = b (v - S'^T k)`` the row written: the same sum, with no third
    walk over 4 MB a slot and layer.

    With a ``row`` (a decode step) ``S_in`` is the STACKED leaf [layers of
    this kind, B, heads, keys, values] and the step is taken on that row of
    it: (o, the leaf with the row advanced). On a TPU that is the Pallas
    kernel, ONE pass over the row where it lies (it takes the leaf and the
    row and writes where it read; the read-out comes from the new state
    while a block is in VMEM); elsewhere the two passes below between a
    slice of the row and an update back."""
    if row is not None:
        if on_tpu():
            from picotron_tpu.ops.pallas.kda_step import kda_step_stacked

            return kda_step_stacked(q, k, v, g, b, S_in, row)
        o, state = kda_step(q, k, v, g, b,
                            lax.dynamic_index_in_dim(S_in, row, 0, False))
        return o, lax.dynamic_update_index_in_dim(S_in, state, row, 0)
    q32, k32, v32 = (a[:, 0].astype(F32) for a in (q, k, v))
    decayed = jnp.exp(g[:, 0])[..., None] * S_in  # [B, nh, K, V]
    along_k = jnp.sum(decayed * k32[..., None], axis=-2)  # [B, nh, V]
    along_q = jnp.sum(decayed * q32[..., None], axis=-2)
    u = b[:, 0, :, None] * (v32 - along_k)
    state = decayed + k32[..., None] * u[..., None, :]
    o = along_q + jnp.sum(k32 * q32, axis=-1, keepdims=True) * u
    return o[:, None], state


def kda_scan(q, k, v, g, b, S_in, chunk: int = SUB_CHUNK) -> tuple:
    """The recurrence over a whole block of rows, ``chunk`` at a time in
    matmul form (the WY / UT transform): (o [B, S, heads, values] float32,
    the state after the last row). Operands as ``kda_step``'s with S rows.

    Within a sub-chunk, with ``G_t = cumsum(g)`` and ``S_0`` the state it
    starts from: ``A[t, s] = b_t (k_t * exp(G_t - G_s)) . k_s`` for ``s <
    t``; ``(I + A) [W | U] = Diag(b) [K * exp(G) | V]`` (a unit
    lower-triangular solve); ``U' = U - W S_0``, the rows written; ``o_t =
    (q_t * exp(G_t))^T S_0 + sum_{s <= t} ((q_t * exp(G_t - G_s)) . k_s)
    u'_s``; ``S_Q = Diag(exp(G_Q)) S_0 + sum_s (k_s * exp(G_Q - G_s))
    u'_s^T``. Every exponent is a difference ``G_t - G_s`` with ``s <= t``,
    taken before the exp: ``exp(-G_s)`` alone overflows float32 where a
    channel decays hard (``g`` ~ -8 a row passes e^88 in eleven rows), so
    the decays between two rows are an elementwise [t, s, channel] block
    and not a product of two matmul operands. Float32 throughout."""
    B, S, nh, K = k.shape
    V = v.shape[-1]
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        # rows past the block: g 0 and b 0, so they leave the state alone
        q, k, v, g, b = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                 (a.ndim - 2)) for a in (q, k, v, g, b))

    def chunks(a):  # [B, S, nh, ...] -> [S / Q, B, nh, Q, ...]
        a = jnp.moveaxis(a.astype(F32), 2, 1)
        return jnp.moveaxis(a.reshape(B, nh, -1, Q, *a.shape[3:]), 2, 0)

    upto = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]  # s <= t

    def one(state, c):
        q_c, k_c, v_c, g_c, b_c = c  # [B, nh, Q, K | V | -]
        G = jnp.cumsum(g_c, axis=-2)
        # k_s * exp(G_t - G_s) for s <= t, 0 elsewhere: [B, nh, t, s, K]
        between = jnp.exp(jnp.where(
            upto[:, :, None], G[..., :, None, :] - G[..., None, :, :],
            -jnp.inf)) * k_c[..., None, :, :]
        kk = jnp.einsum("bhtc,bhtsc->bhts", k_c, between, precision=HIGHEST)
        qk = jnp.einsum("bhtc,bhtsc->bhts", q_c, between, precision=HIGHEST)
        A = kk * b_c[..., None]  # the solve reads it below the diagonal
        from_start = jnp.exp(G)  # [B, nh, Q, K]
        rhs = b_c[..., None] * jnp.concatenate(
            [k_c * from_start, v_c], axis=-1)
        # I + A: the diagonal is taken as 1 and, like what lies above it,
        # not read
        WU = lax.linalg.triangular_solve(
            A, rhs, left_side=True, lower=True, unit_diagonal=True)
        W, U = WU[..., :K], WU[..., K:]
        written = U - jnp.einsum("bhtc,bhcv->bhtv", W, state,
                                 precision=HIGHEST)
        o = jnp.einsum("bhtc,bhcv->bhtv", q_c * from_start, state,
                       precision=HIGHEST) \
            + jnp.einsum("bhts,bhsv->bhtv", qk, written, precision=HIGHEST)
        to_end = jnp.exp(G[..., -1:, :] - G) * k_c  # [B, nh, Q, K]
        state = from_start[..., -1, :, None] * state + jnp.einsum(
            "bhsc,bhsv->bhcv", to_end, written, precision=HIGHEST)
        return state, o

    state, o = lax.scan(one, S_in, tuple(chunks(a) for a in (q, k, v, g, b)))
    # [S / Q, B, nh, Q, V] -> [B, S, nh, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2).reshape(B, nh, -1, V), 1, 2)
    return o[:, :S], state
