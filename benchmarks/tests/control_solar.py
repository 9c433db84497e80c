#!/usr/bin/env python3
"""The serving check's readings for a cell of the Solar Open 2 block, by hand
on the chip at the cell's own size (PERF.md has them):

    python3 benchmarks/tests/control_solar.py --workload <cell> \\
        --seeds a b c

For each seed it prints the sound program's reading (max |err| / max |logit|
over the check's five positions, the number held to ``TOL_LOGITS_REL``), the
device's peak memory after the weights, the program and the reference, and,
along the sound run's tokens, the readings of the program with one fault
each, which the limit has to lie under:

- ``state_not_carried``: every prefill chunk starts from a zero delta-rule
  state (a prompt's chunks after the first forget what came before them);
- ``conv_tail_not_carried``: every prefill chunk starts from an empty conv
  tail (its first three rows convolve with zeros);
- ``pad_rows_advance``: the pad rows of the prompt's last chunk advance the
  state and the conv tail like real ones (the check's prompt is not a whole
  number of chunks, so it has some);
- ``b_not_doubled``: the write strength left in (0, 1), as without
  ``kda_allow_neg_eigval``;
- ``decay_after_write``: the read along ``k`` and the write on the state as
  it was, the decay over the result;
- ``one_decay_a_head``: every key channel of a head decays by the head's
  mean ``g`` (a scalar decay a head, ``ops/ssm.py``'s kind);
- ``qk_not_normalised``: q and k as the convs leave them;
- ``read_out_old``: ``o_t`` read from ``S_{t-1}``, before this token's decay
  and write;
- ``gqa_gate_left_out``: the GQA layers' output not gated;
- ``gqa_rotated``: the GQA layers' q and k rotated (RoPE, base
  ``rope_theta``, over the whole head), where ``use_rope`` is false;
- ``state_bf16``: the delta-rule state rounded to bfloat16 wherever it is
  stored (the nearest precision below the float32 the configuration states
  for it).

The logits may not tell a state stored in bfloat16 from the sound program
(every activation beside it is rounded to bfloat16 too), so each reading
comes with ``bf16_exact``: the share of the slot's state entries, after the
check's last decode step, that bfloat16 holds exactly (``control_granite``).

A fault is a wrapper around the block's own function, put in place before
the engine that runs it is built, and taken away after. The two faults of
the recurrence's order (``RECURRENCES``) run it a token at a time, prefill
and decode alike.
"""

import contextlib
import gc
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.tests import control_granite as granite  # noqa: E402
from benchmarks.tests import test_control as control  # noqa: E402
from benchmarks.tests.control_dsv32 import bare_engine  # noqa: E402

FAULTS = ("state_not_carried", "conv_tail_not_carried", "pad_rows_advance",
          "b_not_doubled", "decay_after_write", "one_decay_a_head",
          "qk_not_normalised", "read_out_old", "gqa_gate_left_out",
          "gqa_rotated", "state_bf16")
RECURRENCES = ("decay_after_write", "read_out_old")


def _recurrence(name):
    """(scan, step) of the delta rule a token at a time with the fault
    ``name``, under ``ops/kda.py``'s signatures."""
    import jax.numpy as jnp
    from jax import lax

    F32 = jnp.float32

    def one(state, t):  # state [B, nh, K, V]; a token's operands [B, nh, ..]
        q, k, v, g, b = t
        a = jnp.exp(g)[..., None]
        read = lambda s, x: jnp.einsum("bhkv,bhk->bhv", s, x)
        write = lambda s: s + (b[..., None] * (v - read(s, k)))[
            ..., None, :] * k[..., None]
        if name == "decay_after_write":
            new = a * write(state)
            return new, read(new, q)
        new = write(a * state)
        return new, read(state if name == "read_out_old" else new, q)

    def scan(q, k, v, g, b, S_in, chunk=None):
        rows = tuple(jnp.moveaxis(x.astype(F32), 1, 0)
                     for x in (q, k, v, g, b))
        state, o = lax.scan(one, S_in, rows)
        return jnp.moveaxis(o, 0, 1), state

    def step(q, k, v, g, b, S_in, row=None):
        if row is None:
            return scan(q, k, v, g, b, S_in)
        o, state = scan(q, k, v, g, b,
                        lax.dynamic_index_in_dim(S_in, row, 0, False))
        return o, lax.dynamic_update_index_in_dim(S_in, state, row, 0)

    return scan, step


def _rotating(kv_cache, theta: float):
    """``kv_cache``'s three functions the GQA layer calls, with q and k
    rotated by position (rotate-half RoPE over the whole head)."""
    import jax.numpy as jnp

    def rotate(x, first):  # x [B, S, heads, D] from position first [B]
        B, S, _, D = x.shape
        at = first[:, None] + jnp.arange(S)[None, :]
        inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
        ang = at[..., None].astype(jnp.float32) * inv  # [B, S, D / 2]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None]
        x32 = x.astype(jnp.float32)
        half = jnp.concatenate([-x32[..., D // 2:], x32[..., :D // 2]], -1)
        return (x32 * cos + half * sin).astype(x.dtype)

    def decode_attention(q, k, v, lengths, scale):
        zero = jnp.zeros((q.shape[0],), jnp.int32)
        return kv_cache.decode_attention(rotate(q, zero), rotate(k, zero), v,
                                         lengths, scale)

    def cache_write(cache, k, v, pos, row):
        return kv_cache.cache_write(cache, rotate(k, pos), v, pos, row)

    def attend(q, cache, lengths, scale, row, impl="dense"):
        return kv_cache.attend(rotate(q, lengths - q.shape[1]), cache,
                               lengths, scale, row, impl=impl)

    return types.SimpleNamespace(
        decode_attention=decode_attention, cache_write=cache_write,
        attend=attend, row_major=kv_cache.row_major)


@contextlib.contextmanager
def fault(name):
    """The block with one fault (None: sound), for the engines built
    inside."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.models import solar_open2 as so

    names = ("kda_mixer", "kda_scan", "kda_step", "l2_normalise",
             "gqa_layer", "kv_cache")
    kept = {n: getattr(so, n) for n in names}
    mixer, gqa_layer = so.kda_mixer, so.gqa_layer

    def rounded(x):
        # an explicit op: the compiler drops a convert there and back
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)

    def faulty_mixer(lp, x, conv_in, state_in, live, m, one_step):
        if not one_step:
            if name == "state_not_carried":
                state_in = jnp.zeros_like(state_in)
            if name == "conv_tail_not_carried":
                conv_in = jnp.zeros_like(conv_in)
        if name == "pad_rows_advance":
            live = jnp.ones_like(live)
        if name == "state_bf16":
            state_in = rounded(state_in)
        out, conv_out, state_out = mixer(lp, x, conv_in, state_in, live, m,
                                         one_step)
        if name == "state_bf16":
            state_out = rounded(state_out)
        return out, conv_out, state_out

    def operands(change):
        """The sound recurrence on changed operands ``(g, b)``."""
        return [lambda q, k, v, g, b, *rest, fn=fn: fn(q, k, v, *change(g, b),
                                                       *rest)
                for fn in (kept["kda_scan"], kept["kda_step"])]

    if name in ("state_not_carried", "conv_tail_not_carried",
                "pad_rows_advance", "state_bf16"):
        so.kda_mixer = faulty_mixer
    if name == "b_not_doubled":
        so.kda_scan, so.kda_step = operands(lambda g, b: (g, 0.5 * b))
    if name == "one_decay_a_head":
        so.kda_scan, so.kda_step = operands(lambda g, b: (
            jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape),
            b))
    if name in RECURRENCES:
        so.kda_scan, so.kda_step = _recurrence(name)
    if name == "qk_not_normalised":
        so.l2_normalise = lambda x: x.astype(jnp.float32)
    if name == "gqa_gate_left_out":
        so.gqa_layer = lambda lp, *a, **kw: gqa_layer(
            {n: v for n, v in lp.items() if n != "wg"}, *a, **kw)
    if name == "gqa_rotated":
        so.kv_cache = _rotating(kept["kv_cache"], 10000.0)
    try:
        yield
    finally:
        for n, v in kept.items():
            setattr(so, n, v)


def reading(ctx, sound, params, name) -> tuple:
    """``control_granite.reading`` with this block's state leaf (``kda``)."""
    with fault(name):
        engine = bare_engine(ctx)
        step, kept = engine.decode_step, {}

        def decode_step(*args):
            out = step(*args)
            kept["cache"] = out[0]
            return out

        engine.decode_step = decode_step
        ok, rows = control.control_reading(sound, engine, params)
        share = granite.bf16_exact_share(kept["cache"]["kda"][:, 0])
    del engine, kept
    gc.collect()
    return control.worst(rows), ok, share


if __name__ == "__main__":
    # the record and the summary are ``control_granite``'s (a state beside
    # K/V, ``bf16_exact`` of slot 0's state), run over this block's faults
    granite.fault, granite.FAULTS, granite.reading = fault, FAULTS, reading
    sys.exit(granite.main())
