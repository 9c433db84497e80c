"""The diagonal-decay recurrence three recurrent mixers run over a float32
state ``S[h]`` [d_head, d_state]: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x)
B_t``, ``y_t = S_t C_t``: a scalar decay a head and a plain rank-one add. (The
repo's second recurrence is ``ops/kda.py``, ``models/solar_open2.py``'s gated
delta rule: a decay for every key channel, and a write that first READS the
state along the key; no operands given to the functions here compute it.)
``models/granite_hybrid.py`` (Mamba-2: ``dt`` from the input, one
``B``/``C`` for all heads) and ``models/minicpm_sala.py``
(lightning attention: ``dt = 1``, ``A = -slope``, ``x = v``, ``B = k``, ``C
= q``, each per head) prefill with the chunked scan in matmul form
(``ssm_scan``) and decode with the one-row step (``ssm_step``), taken on the
layer's row of the stacked state leaf (``row=``): on a TPU one Pallas kernel
that passes over that row once, in place
(``ops/pallas/ssm_step.py::ssm_step_stacked``: read, decay, add, write back,
read out), and off one (every CPU test and drive) the elementwise step
between a slice of the row and an update back. The platform and the
operands' shapes choose, as ``quant_matmul`` chooses its form: no option
does.

``Bm``/``Cm`` are [B, S, d_state] (shared by the heads), [B, S, heads,
d_state] (a head's own) or [B, S, groups, d_state] with fewer groups than
heads (``models/nemotron_h.py``: eight groups of sixteen heads; head ``h``
reads group ``h // (heads / groups)``); the rank picks the contraction, and a
group's heads are the shared form, a group at a time (``_by_group``: B and C
are never written out a head). ``dt = 0`` leaves the state exactly as it is (``exp(0) S + 0``):
how a pad row or a parked slot is kept out of it."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.utils import on_tpu

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def _by_group(fn, xs, dt, A, Bm, Cm, S_in) -> tuple:
    """``fn`` (``ssm_scan`` or ``ssm_step``) with ``Bm``/``Cm`` [B, S,
    groups, d_state], a group of neighbouring heads to each: the shared form
    over a group's heads, mapped over the groups."""
    B, S, nh, hd = xs.shape
    G = Bm.shape[2]
    R = nh // G
    y, state = jax.vmap(fn, in_axes=(2, 2, 0, 2, 2, 1), out_axes=(2, 1))(
        xs.reshape(B, S, G, R, hd), dt.reshape(B, S, G, R), A.reshape(G, R),
        Bm, Cm, S_in.reshape(B, G, R, *S_in.shape[2:]))
    return y.reshape(B, -1, nh, hd), state.reshape(S_in.shape)


def _grouped(xs, Bm) -> bool:
    return Bm.ndim == 4 and Bm.shape[2] != xs.shape[2]


def ssm_scan(xs, dt, A, Bm, Cm, S_in, chunk: int) -> tuple:
    """The recurrence over a whole block of rows, ``chunk`` at a time in
    matmul form: (y [B, S, heads, d_head] float32 without the ``D`` skip,
    the state after the last row). ``xs`` [B, S, heads, d_head], ``dt`` [B,
    S, heads] float32 (0: the row leaves the state as it is), ``A`` [heads],
    ``Bm``/``Cm`` [B, S, d_state], [B, S, heads, d_state] or [B, S, groups,
    d_state], ``S_in`` [B, heads, d_head, d_state] float32. Within a chunk, with ``L = cumsum(dt
    A)``: ``Y = ((C B^T) * exp(L_t - L_s) * [s <= t]) (dt x) + exp(L_t) C
    S_in`` and ``S_out = exp(L_Q) S_in + sum_s exp(L_Q - L_s) dt_s x_s (x)
    B_s``."""
    if _grouped(xs, Bm):
        return _by_group(partial(ssm_scan, chunk=chunk), xs, dt, A, Bm, Cm,
                         S_in)
    B, S, nh, hd = xs.shape
    per_head = Bm.ndim == 4
    Q = min(chunk, S)
    pad = -S % Q
    if pad:
        # rows past the block: dt 0, so they leave the state alone
        xs, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) *
                                  (a.ndim - 2)) for a in (xs, dt, Bm, Cm))

    def chunks(a):  # [B, S, ...] -> [S / Q, B, Q, ...]
        return jnp.moveaxis(a.reshape(B, -1, Q, *a.shape[2:]), 1, 0)

    tri = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]

    def one(state, c):
        x_c, dt_c, B_c, C_c = c
        L = jnp.cumsum(jnp.moveaxis(dt_c * A, 2, 1), axis=-1)  # [B, nh, Q]
        decay = jnp.exp(jnp.where(tri, L[..., :, None] - L[..., None, :],
                                  -jnp.inf))  # [B, nh, t, s]
        xdt = x_c.astype(F32) * dt_c[..., None]  # [B, Q, nh, hd]
        C32, B32 = C_c.astype(F32), B_c.astype(F32)
        if per_head:
            G = jnp.einsum("bthn,bshn->bhts", C_c, B_c,
                           preferred_element_type=F32) * decay
            read = jnp.einsum("bthn,bhpn->bthp", C32, state,
                              precision=HIGHEST)
        else:
            G = jnp.einsum("btn,bsn->bts", C_c, B_c,
                           preferred_element_type=F32)[:, None] * decay
            read = jnp.einsum("btn,bhpn->bthp", C32, state,
                              precision=HIGHEST)
        y = jnp.einsum("bhts,bshp->bthp", G, xdt)
        y = y + read * jnp.moveaxis(jnp.exp(L), 1, 2)[..., None]
        to_end = jnp.moveaxis(jnp.exp(L[..., -1:] - L), 1, 2)  # [B, Q, nh]
        state = jnp.exp(L[..., -1])[..., None, None] * state + jnp.einsum(
            "bshp,bshn->bhpn" if per_head else "bshp,bsn->bhpn",
            xdt * to_end[..., None], B32, precision=HIGHEST)
        return state, y

    state, y = lax.scan(one, S_in, tuple(chunks(a)
                                         for a in (xs, dt, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1).reshape(B, -1, nh, hd)
    return y[:, :S], state


def ssm_step(xs, dt, A, Bm, Cm, S_in, row=None) -> tuple:
    """One row a sequence, the recurrence as it is written: (y [B, 1,
    heads, d_head] float32 without the skip, the new state). One pass over
    the state: elementwise in float32, the read-out a sum over d_state.

    With a ``row`` (a decode step) ``S_in`` is the STACKED leaf [layers of
    this kind, B, heads, d_head, d_state] and the step is taken on that row
    of it: (y, the leaf with the row advanced). On a TPU that is the Pallas
    kernel, which takes the leaf and the row and writes where it read (a
    slice handed to it would be a copy of the layer in front of it and
    another behind); elsewhere the step below on the row."""
    if row is not None:
        if on_tpu():
            from picotron_tpu.ops.pallas.ssm_step import ssm_step_stacked

            return ssm_step_stacked(xs, dt, A, Bm, Cm, S_in, row)
        y, state = ssm_step(xs, dt, A, Bm, Cm,
                            lax.dynamic_index_in_dim(S_in, row, 0, False))
        return y, lax.dynamic_update_index_in_dim(S_in, state, row, 0)
    if _grouped(xs, Bm):
        return _by_group(ssm_step, xs, dt, A, Bm, Cm, S_in)

    def over_heads(a):  # -> broadcasts against [B, heads, d_head, d_state]
        a = a[:, 0].astype(F32)
        return a[:, :, None, :] if a.ndim == 3 else a[:, None, None, :]

    x32 = xs[:, 0].astype(F32) * dt[:, 0, :, None]  # [B, nh, hd]
    state = jnp.exp(dt[:, 0] * A)[..., None, None] * S_in \
        + x32[..., None] * over_heads(Bm)
    y = jnp.sum(state * over_heads(Cm), axis=-1)
    return y[:, None], state
