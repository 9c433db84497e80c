"""The serve runner for a block whose layers keep a float32 recurrent state
beside their K/V rows (``LEAVES`` holds ``ssm``): ``runners/serve.py`` as it
is (its engine, warm-up, load, window, traced tail and limits), with a check
that reads the state as well as the logits.

``serve.logits_check`` compares five rows of logits, and those cannot tell a
state stored in bfloat16 from the float32 the configuration states: every
activation beside the state is rounded to bfloat16 already, and a state
rounded wherever it is stored moves the worst logit by nothing that shows
(PERF.md section 6, PR 65: control (h) reads as the sound program does, seed
for seed). So behind the prompt's prefill and the check's decode steps the
check here reads slot 0's ``ssm`` leaf, every layer's, and holds it to the
state the reference's row-by-row float32 recurrence has behind the same
tokens (``reference.forward_logits_and_state``: one forward gives the logits
and the states). Four numbers, and each has to hold (PERF.md section 6, PR
65, has every control's reading of each, twelve seeds on the chip):

1. **The logits' worst row**, ``serve.compare_logits`` under
   ``serve.TOL_LOGITS_REL``, as ``serve.logits_check`` has it: max |err| of
   a row within 3 % of its max |logit| (the sound program 0.85-1.12 %).
2. **The logits' error as a whole** (``TOL_LOGITS_RMS``): the root mean
   square of the error over the five rows against the root mean square of
   the reference's logits there. A row's worst logit of 261,120 moves by a
   quarter from seed to seed, the mean over 1.3 million does not (the sound
   program 0.83-0.92 %), so the limit can lie nearer: a prompt's first 512
   K/V rows left unwritten read 3.6-4.0 % here and 3.9-5.1 % by number 1,
   every matrix of a layer in E4M3 7.2-7.7 % and 7.5-8.7 %.
3. **The state's values** (``TOL_STATE_RMS``): rms |program - reference|
   over all of slot 0's entries against the rms of the reference's. The
   program's state is float32 arithmetic on inputs that went through
   bfloat16 (the normed stream, ``in_proj``'s output, the conv's), so this
   reads 0.76-0.93 % whatever the state is stored in (rounded to bfloat16
   wherever it is stored: 0.79-0.97 %, 2 to 15 % above the same seed's
   sound reading, inside the spread from seed to seed); it is there for a
   state that is wrong (dropped at a chunk boundary 3.1-18 %, advanced by
   what a stale K/V row let through 2.8-3.7 %), which the logits hear only
   through the heads that remember that far.
4. **The state's resolution** (``TOL_BF16_EXACT``): the share of those
   entries, zeros left out, that bfloat16 holds exactly. An entry of a
   float32 recurrence lies anywhere between two bfloat16 neighbours, and one
   in 65,536 lands on one by chance (the sound program reads 0.00003 on
   every seed, of 4.2 million entries); an entry that was stored in
   bfloat16, or in anything coarser that bfloat16 holds, on its way lands on
   one always, and a state rounded wherever it is stored reads 1.0. This
   reads the program's leaf alone, because held against the reference's
   state the two cannot be told apart (number 3): the inputs' rounding is
   three times the state's own.

``serve.run`` looks ``logits_check`` up in its module when it runs, and is
handed this one there for the length of the call (as ``runners/
serve_blocks.py`` does; PERF.md section 7 item 41 asks the next benchmark PR
for a parameter instead).
"""

from __future__ import annotations

import numpy as np

from benchmarks.runners import serve
from benchmarks.runners.serve_blocks import rms_rel

# rms |err| / rms |logit| over the check's five rows: the sound program
# 0.83-0.92 %, a first chunk's K/V rows left unwritten 3.6 % and more
TOL_LOGITS_RMS = {"bfloat16": 1.8e-2, "float32": 1e-3}
# rms |err| / rms |entry| over slot 0's state of every layer, behind the
# check's prompt and decode steps: the sound program 0.76-0.93 % (and a
# state kept in bfloat16 0.79-0.97 %), a stale K/V row's wake 2.8 % and more
TOL_STATE_RMS = {"bfloat16": 1.7e-2, "float32": 1e-3}
# the largest share of slot 0's state entries that bfloat16 may hold
# exactly: float32 reads 0.00003, a state rounded to bfloat16 wherever it is
# stored 1.0, one layer of four kept in bfloat16 0.25
TOL_BF16_EXACT = 1e-2


def program_logits_and_state(engine, params, prompt, follow=None) -> tuple:
    """``serve.program_logits`` and, behind its last decode step, slot 0's
    ``ssm`` rows [layers, heads, d_head, d_state] as numpy float32."""
    step, kept = engine.decode_step, {}

    def decode_step(*args):
        out = step(*args)
        kept["cache"] = out[0]
        return out

    engine.decode_step = decode_step
    try:
        seq, got = serve.program_logits(engine, params, prompt, follow)
    finally:
        engine.decode_step = step
    return seq, got, np.asarray(kept.pop("cache")["ssm"][:, 0], np.float32)


def reference_logits_and_state(ctx, params, seq, n_prompt: int) -> tuple:
    """The reference's logits at the positions ``program_logits`` reads, and
    its state behind ``seq``'s last token, of one forward."""
    import jax

    logits, state = ctx["reference"].forward_logits_and_state(
        params, np.asarray([seq], np.int32), ctx["config"], jax.devices()[0])
    return logits[0][n_prompt - 1:], state[0]


def bf16_exact_share(state) -> float:
    """Share of the entries of ``state`` (float32) other than 0 that
    bfloat16 holds exactly."""
    import jax.numpy as jnp

    state = np.asarray(state, np.float32)
    exact = np.asarray(jnp.asarray(state).astype(jnp.bfloat16)
                       .astype(jnp.float32)) == state
    there = state != 0
    return float(np.sum(exact & there) / max(np.sum(there), 1))


def extra_rows(got, want, state, want_state, dtype: str) -> list:
    """Rows of (what, err, scale, 0, ok) for numbers 2 to 4."""
    err, scale = rms_rel(got, want)
    s_err, s_scale = rms_rel(state, want_state)
    exact = bf16_exact_share(state)
    return [(f"all {len(got)} rows, root mean square {err / scale:.3%} of the "
             f"logits' for max", err, scale, 0.0,
             err <= TOL_LOGITS_RMS[dtype] * scale),
            (f"state of slot 0, {state.size} entries, rms "
             f"{s_err / s_scale:.3%} of the reference's for max", s_err,
             s_scale, 0.0,
             s_err <= TOL_STATE_RMS[dtype] * s_scale),
            (f"state of slot 0, share of entries bfloat16 holds exactly "
             f"{exact:.5f} for max", exact, 1.0, 0.0,
             exact <= TOL_BF16_EXACT)]


def logits_check(ctx, engine, params, prompt) -> tuple:
    """(ok, rows of (what, err, scale, margin, ok)): ``serve.logits_check``'s
    contract, the three rows of numbers 2 to 4 behind the logits'."""
    dtype = ctx["config"].get("torch_dtype", "bfloat16")
    seq, got, state = program_logits_and_state(engine, params, prompt)
    want, want_state = reference_logits_and_state(ctx, params, seq,
                                                  len(prompt))
    ok, rows = serve.compare_logits(got, want, serve.TOL_LOGITS_REL[dtype])
    rows += extra_rows(got, want, state, want_state, dtype)
    return ok and all(r[-1] for r in rows[-3:]), rows


def run(ctx: dict) -> dict:
    kept, serve.logits_check = serve.logits_check, logits_check
    try:
        return serve.run(ctx)
    finally:
        serve.logits_check = kept
