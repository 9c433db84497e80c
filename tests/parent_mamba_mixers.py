"""The Mamba-2 mixers of ``models/granite_hybrid.py`` and
``models/nemotron_h.py`` as they stood before PR 65 lifted them into
``models/mamba2.py``, word for word (commit 82ad1ba): what
``tests/test_falcon_h1.py`` holds the lifted mixer to, bit for bit, on the
two blocks' toy shapes. Not a test file.

It is PR 65's proof that the lift moved no bit, and nothing else runs it. It
pins ``mamba2.mixer`` to this copy from then on, which a later change to the
mixer's arithmetic has no reason to honour: once ``tests/
test_granite_hybrid.py`` and ``tests/test_nemotron_h.py``, which hold the
two blocks to their own references, are judged enough, this file and the two
cases of ``test_the_lifted_mixer_is_the_parents`` go together (the cases'
count made up with tests of what stays)."""

import jax
import jax.numpy as jnp

from picotron_tpu.config import ModelConfig
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.ssm import ssm_scan, ssm_step

F32 = jnp.float32


def granite_mixer(lp, x, conv_in, ssm_in, live, m: ModelConfig,
                one_step: tuple) -> tuple:
    """The mixer on the normed stream ``x`` [B, S, H] from the conv's last
    inputs ``conv_in`` [B, d_conv - 1, width] and the state ``ssm_in``:
    (output [B, S, H], the conv's last inputs and the state behind the last
    ``live`` row). ``live`` [B, S] marks the real rows, a leading run of
    each sequence. ``one_step`` is empty, or on a decode step ``(row,)``:
    ``ssm_in`` is then the whole stacked leaf and so is the state returned,
    that row of it advanced where it lies (``ops/ssm.py::ssm_step``)."""
    B, S, _ = x.shape
    nh, hd, N, K = (m.mamba_n_heads, m.mamba_d_head, m.mamba_d_state,
                    m.mamba_d_conv)
    Di = nh * hd
    with jax.named_scope("ssm_proj"):
        proj = x @ lp["in_proj"]
        z, u, dt = proj[..., :Di], proj[..., Di:-nh], proj[..., -nh:]
    with jax.named_scope("ssm_conv"):
        padded = jnp.concatenate([conv_in.astype(u.dtype), u], axis=1)
        w = lp["conv_w"].astype(F32)
        conv = lp["conv_b"].astype(F32) + sum(
            padded[:, j:j + S].astype(F32) * w[:, j] for j in range(K))
        u = jax.nn.silu(conv).astype(x.dtype)
        # the last inputs behind the last live row: rows n .. n + K - 2 of
        # the padded block, n the live rows (0: the tail stays as it was)
        at = jnp.sum(live, axis=1, dtype=jnp.int32)[:, None] \
            + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        conv_out = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    xs = u[..., :Di].reshape(B, S, nh, hd)
    Bm, Cm = u[..., Di:Di + N], u[..., Di + N:]
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"]) \
        * live[..., None].astype(F32)
    A = -jnp.exp(lp["A_log"])
    if one_step:
        with jax.named_scope("ssm_step"):
            y, ssm_out = ssm_step(xs, dt, A, Bm, Cm, ssm_in, *one_step)
    else:
        with jax.named_scope("ssm_scan"):
            y, ssm_out = ssm_scan(xs, dt, A, Bm, Cm, ssm_in,
                                  m.mamba_chunk_size)
    with jax.named_scope("ssm_gate_out"):
        y = y + lp["D"][:, None] * xs.astype(F32)
        y = y.reshape(B, S, Di) * jax.nn.silu(z.astype(F32))
        y = rms_norm(y, lp["gate_norm"], m.rms_norm_eps).astype(x.dtype)
        out = y @ lp["out_proj"]
    return out, conv_out, ssm_out


def nemotron_mixer(lp, x, conv_in, ssm_in, live, m: ModelConfig,
                one_step: tuple) -> tuple:
    """The mixer on the normed stream ``x`` [B, S, H] from the conv's last
    inputs ``conv_in`` [B, conv_kernel - 1, width] and the state ``ssm_in``:
    (output [B, S, H], the conv's last inputs and the state behind the last
    ``live`` row). ``live`` [B, S] marks the real rows, a leading run of
    each sequence. ``one_step`` is empty, or on a decode step ``(row,)``:
    ``ssm_in`` is then the whole stacked leaf and so is the state returned,
    that row of it advanced where it lies (``ops/ssm.py::ssm_step``)."""
    B, S, _ = x.shape
    nh, hd, N, K, G = (m.mamba_num_heads, m.mamba_head_dim, m.ssm_state_size,
                       m.conv_kernel, m.n_groups)
    Di = nh * hd
    with jax.named_scope("ssm_proj"):
        proj = x @ lp["in_proj"]
        z, u, dt = proj[..., :Di], proj[..., Di:-nh], proj[..., -nh:]
    with jax.named_scope("ssm_conv"):
        padded = jnp.concatenate([conv_in.astype(u.dtype), u], axis=1)
        w = lp["conv_w"].astype(F32)
        conv = lp["conv_b"].astype(F32) + sum(
            padded[:, j:j + S].astype(F32) * w[:, j] for j in range(K))
        u = jax.nn.silu(conv).astype(x.dtype)
        # the last inputs behind the last live row: rows n .. n + K - 2 of
        # the padded block, n the live rows (0: the tail stays as it was)
        at = jnp.sum(live, axis=1, dtype=jnp.int32)[:, None] \
            + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        conv_out = jnp.take_along_axis(padded, at[:, :, None], axis=1)
    xs = u[..., :Di].reshape(B, S, nh, hd)
    Bm = u[..., Di:Di + G * N].reshape(B, S, G, N)
    Cm = u[..., Di + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"]) \
        * live[..., None].astype(F32)
    A = -jnp.exp(lp["A_log"])
    if one_step:
        with jax.named_scope("nemotron/ssm_step"):
            y, ssm_out = ssm_step(xs, dt, A, Bm, Cm, ssm_in, *one_step)
    else:
        with jax.named_scope("nemotron/ssm_scan"):
            y, ssm_out = ssm_scan(xs, dt, A, Bm, Cm, ssm_in, m.chunk_size)
    with jax.named_scope("ssm_gate_out"):
        y = y + lp["D"][:, None] * xs.astype(F32)
        y = y.reshape(B, S, Di) * jax.nn.silu(z.astype(F32))
        # the mean square over each group's channels, not over all of them
        y = rms_norm(y.reshape(B, S, G, Di // G),
                     lp["gate_norm"].reshape(G, Di // G), m.rms_norm_eps)
        out = y.reshape(B, S, Di).astype(x.dtype) @ lp["out_proj"]
    return out, conv_out, ssm_out
