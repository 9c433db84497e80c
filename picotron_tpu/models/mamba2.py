"""The Mamba-2 mixer the three blocks that hold one run (``granite_hybrid``,
``nemotron_h``, ``falcon_h1``), written once, over shapes and not over a
``ModelConfig``'s key names (each block reads its own published keys and
hands the numbers over), and the seeded draw of its parameters.

``u`` the normed stream, ``d_inner = heads * d_head``, ``G`` groups of
neighbouring heads that share a ``B``/``C`` row, ``N = d_state``: ``[z | x B
C | dt] = (u W_in) * col_mult`` (``d_inner | d_inner + 2 G N | heads``;
``col_mult`` a vector over the columns, or none); ``[x | B | C]_t <- silu(b
+ sum_j w[:, j] [x | B | C]_{t - (d_conv - 1) + j})`` (causal, depthwise,
zeros before the sequence); ``dt = softplus(dt + dt_bias)``, ``A =
-exp(A_log)`` a head; the float32 state ``S[h]`` [d_head, N]: ``S_t =
exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t[g(h)]``, ``y_t = S_t C_t[g(h)] + D
x_t``; ``y <- w * RMSNorm(y * silu(z))`` (gate first), the mean square over
each group's channels; ``W_out``.

A prefill runs the recurrence as the chunked scan in matmul form, a decode
step as the one-row step on the layer's row of the stacked state leaf
(``ops/ssm.py``). ``dt = 0`` where a row is not ``live`` freezes ``S``
exactly, and the conv tail is taken behind the last live row.

The scan, the step and the norm are the CALLER's, handed over by name: each
block names them in its own module, where the benchmark's controls put a
fault (``benchmarks/tests/control_{granite,nemotron,falcon}.py``), and wraps
this function as its ``mamba_mixer(lp, x, conv_in, ssm_in, live, m,
one_step)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def mixer(lp, x, conv_in, ssm_in, live, one_step: tuple, *, heads: int,
          d_head: int, d_state: int, d_conv: int, groups: int, chunk: int,
          eps: float, scan, step, norm, scope: str = "",
          col_mult=None) -> tuple:
    """The mixer on the normed stream ``x`` [B, S, H] from the conv's last
    inputs ``conv_in`` [B, d_conv - 1, width] and the state ``ssm_in``:
    (output [B, S, H], the conv's last inputs and the state behind the last
    ``live`` row). ``live`` [B, S] marks the real rows, a leading run of
    each sequence. ``one_step`` is empty, or on a decode step ``(row,)``:
    ``ssm_in`` is then the whole stacked leaf and so is the state returned,
    that row of it advanced where it lies (``ops/ssm.py::ssm_step``).
    ``scope`` leads the names of the recurrence's two scopes in a trace."""
    B, S, _ = x.shape
    nh, hd, N, K, G = heads, d_head, d_state, d_conv, groups
    Di = nh * hd
    with jax.named_scope("ssm_proj"):
        proj = x @ lp["in_proj"]
        if col_mult is not None:
            proj = proj * col_mult
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
    Bm, Cm = u[..., Di:Di + G * N], u[..., Di + G * N:]
    if G > 1:  # one group: the row every head shares, as it is
        Bm, Cm = Bm.reshape(B, S, G, N), Cm.reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(F32) + lp["dt_bias"]) \
        * live[..., None].astype(F32)
    A = -jnp.exp(lp["A_log"])
    if one_step:
        with jax.named_scope(scope + "ssm_step"):
            y, ssm_out = step(xs, dt, A, Bm, Cm, ssm_in, *one_step)
    else:
        with jax.named_scope(scope + "ssm_scan"):
            y, ssm_out = scan(xs, dt, A, Bm, Cm, ssm_in, chunk)
    with jax.named_scope("ssm_gate_out"):
        y = y + lp["D"][:, None] * xs.astype(F32)
        y = y.reshape(B, S, Di) * jax.nn.silu(z.astype(F32))
        if G > 1:
            # the mean square over each group's channels, not over all
            y = norm(y.reshape(B, S, G, Di // G),
                     lp["gate_norm"].reshape(G, Di // G),
                     eps).reshape(B, S, Di)
        else:
            y = norm(y, lp["gate_norm"], eps)
        out = y.astype(x.dtype) @ lp["out_proj"]
    return out, conv_out, ssm_out


def draw(keys, n: int, *, heads: int, width: int, d_conv: int,
         dtype) -> dict:
    """The seeded parameters of ``n`` stacked mixers beside their two
    matrices and the gated norm, from four ``keys``, as the Mamba-2
    reference initialises them: conv taps U(+-sqrt(1 / d_conv)) over the
    conv's ``width`` channels with a bias U(+-0.1), ``A_log = log U(1,
    16)``, ``dt_bias`` the inverse softplus of dt log-uniform in [1e-3,
    1e-1], ``D = 1`` (the three a head, in float32)."""
    tap = math.sqrt(1.0 / d_conv)
    step = jnp.exp(jax.random.uniform(
        keys[3], (n, heads), F32, math.log(1e-3), math.log(1e-1)))
    return {
        "conv_w": jax.random.uniform(keys[0], (n, width, d_conv), dtype,
                                     -tap, tap),
        "conv_b": jax.random.uniform(keys[1], (n, width), dtype, -0.1, 0.1),
        "A_log": jnp.log(jax.random.uniform(keys[2], (n, heads), F32, 1.0,
                                            16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "D": jnp.ones((n, heads), F32),
    }


def louder_bc(in_proj, d_inner: int, width: int, gain: float):
    """``in_proj`` with its ``B`` and ``C`` columns (``2 d_inner .. d_inner
    + width``, ``width`` the conv's) drawn ``gain`` times wider: with the
    flat draw the state's read-out ``S C`` is a fiftieth of the skip ``D x``
    beside it, and neither a lost state nor a shifted conv tail moves a
    logit."""
    cols = jnp.arange(in_proj.shape[-1])
    bc = (cols >= 2 * d_inner) & (cols < d_inner + width)
    return in_proj * jnp.where(bc, gain, 1.0).astype(in_proj.dtype)
