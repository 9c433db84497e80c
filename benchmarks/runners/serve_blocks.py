"""The serve runner for a block that generates by diffusion over blocks
(``engine.blocks``): ``runners/serve.py`` as it is (its engine, warm-up, load,
window, traced tail and limits), with another check in the place of
``logits_check``. That one feeds a token a step through ``engine.decode_step``
and compares with a causal full forward, which is not how such a model
generates: a fed token sees the rest of its block.

The check here has three numbers, and each has to hold:

1. **Every denoise forward's logits.** The seeded prompt of
   ``check_prompt_len`` tokens is admitted by a ``ContinuousBatcher`` of its
   own (``admitted``: the batcher's own admission prefills the whole blocks,
   in chunks past ``prefill_chunk``, block-causally, and hands over the
   remainder). Behind it the round program's forwards run one at a time
   (``engine.block_forward``, which exists to read logits: what
   ``_blocks_impl`` runs in its loop): block A (the remainder given, the
   rest masked) denoised to full and committed, block B (all masked)
   denoised to full through the cache that holds A and committed, and block
   C's first denoise forward. Every denoise forward's logits at its masked
   positions are compared with ``reference.forward_logits`` of the very
   sequence the program held at that forward (prompt, finished blocks, the
   block as it stood), under ``serve.compare_logits`` and
   ``serve.TOL_LOGITS_REL``. Which positions a forward fixes is the
   reference's rule (``reference.unmask``) on the program's logits. Then the
   same forwards behind the prompt's first ``block_length + 2`` tokens
   (``short_prompt``, admitted through the one-shot program). Behind 1,538
   tokens the rows of a block are three keys among 1,540 that a query weighs
   alike on seeded weights, and which of them it saw moves no logit by more
   than bfloat16's rounding; behind six they are half of what it sees, and a
   block read causally, a commit left out or a remainder dropped fails the
   limit (PERF.md section 6, PR 62, has both readings of each control).
2. **Their error as a whole** (``rms_rel``): the root mean square of the
   error over all those rows against the root mean square of the reference's
   logits there, held to ``TOL_RMS_REL``. The largest error of a row is set
   by its worst logit of 18,992 and moves by a quarter from seed to seed; the
   mean over 650,000 logits does not, so this is the number that tells the
   program from one computed below the precision the configuration states
   (PERF.md section 6, PR 62: the two readings of the limit).
3. **The round program itself** (``round_streams``, ``round_rows``): before
   the forwards, a batcher serves the same prompt at temperature 0 in
   several slots at once (a budget of both blocks, one that ends inside the
   second, one inside the first; then an EOS inside the second beside the
   whole run once more). The forwards of blocks A and B then FOLLOW the
   whole run: a position is fixed when the reference's rule says so, at the
   token the round streamed there, and that token has to be the forward's
   own argmax or lie within ``ROUND_MARGIN`` of it (two programs that each
   lie within the sound reading of the reference may draw otherwise where
   the two best logits of 18,992 are that close: one stream in four, call 9).
   The other streams have to be, token for token, what the whole run gives
   under their budget or up to their EOS. That holds ``_blocks_impl``'s scan
   and while_loop, the device's unmask rule, the commit inside a round, the
   budget's and the EOS's cut, the left-pack, a slot taken again and the
   batcher's handover of the given positions.

``serve.run`` looks ``logits_check`` up in its module when it runs, and is
handed this one there for the length of the call (PERF.md section 7 asks the
next benchmark PR for a parameter instead).
"""

from __future__ import annotations

import gc

import numpy as np

from benchmarks.runners import serve

# (limit of denoise forwards, None: to full; committed afterwards)
PLAN = ((None, True), (None, True), (1, False))

# rms |err| / rms |logit| over the masked rows of the check's forwards: the
# sound program 0.66-0.70 %, the reference with its layers' matrices in E4M3
# 7.2-8.2 % (PERF.md section 6, PR 62: both readings, and what bfloat16 in
# the program's float32 parts reads)
TOL_RMS_REL = {"bfloat16": 1.5e-2, "float32": 1e-3}
# how far under its forward's largest logit, over the row's max |logit|, a
# token the round streamed may lie: the sound program's own distance from
# the reference in a row (0.68-1.04 %)
ROUND_MARGIN = {"bfloat16": 1e-2, "float32": 1e-3}


def short_prompt(prompt, block_length: int) -> list:
    """The check's second prompt: one whole block and a remainder."""
    return list(prompt[:block_length + min(2, block_length - 1)])


def admitted(engine, params, prompt) -> tuple:
    """(cache, slot, the prompt's remainder) as the batcher's own admission
    leaves them: ``prompt``'s whole blocks prefilled into ``slot``, the rest
    handed to the slot's first block. The batcher is let go; the cache is
    the caller's."""
    from picotron_tpu.inference import ContinuousBatcher, Request

    batcher = ContinuousBatcher(engine, params, seed=0)
    batcher.submit(Request(uid="check", prompt=list(prompt),
                           max_new_tokens=engine.decode_block_len))
    batcher._admit()
    slot = next(i for i, s in enumerate(batcher._slots) if s is not None)
    given = batcher._given[slot, :batcher._given_n[slot]].tolist()
    cache, batcher._cache = batcher._cache, None
    return cache, slot, given


def program_forwards(engine, params, prompt, reference, follow=None,
                     stream=None) -> list:
    """[(sequence held, masked [Bd] bool, logits [Bd, V])] of every denoise
    forward of ``PLAN`` behind ``prompt``. A position is fixed at its
    forward's argmax, or where ``stream`` (the tokens a round streamed
    behind ``prompt``) reaches it, at the stream's token. ``follow``, the
    list a sound run returned, replays its blocks as they stood (a control
    is read along the tokens the sound program chose; its own admission
    says which of the prompt's remainder it was given)."""
    m = engine.cfg.model
    model = {"remasking": m.remasking,
             "confidence_threshold": m.confidence_threshold}
    Bd, steps = m.block_length, m.denoising_steps
    owed = reference.transfer_counts(Bd, steps)
    cache, slot, given = admitted(engine, params, prompt)
    seq, out = list(prompt[:len(prompt) // Bd * Bd]), []
    live = np.arange(engine.slots) == slot

    def forward(cache, block, commit=False):
        fed = np.full((engine.slots, Bd), m.mask_token_id, np.int32)
        fed[slot] = block
        cache, logits = engine.block_forward(params, cache, fed, live, commit)
        return cache, None if commit else np.asarray(logits[slot], np.float32)

    for limit, commit in PLAN:
        block = np.array(given + [m.mask_token_id] * (Bd - len(given)))
        masked = np.arange(Bd) >= len(given)
        for s in range(steps if limit is None else limit):
            if not masked.any():
                break
            if follow is not None:
                held, masked, _ = follow[len(out)]
                block, masked = np.array(held[-Bd:]), masked.copy()
                # a remainder the admission under test did not hand over
                # is fed as the batcher would feed it: masked
                block[len(given):max(len(prompt) - len(seq), 0)] = \
                    m.mask_token_id
            cache, logits = forward(cache, block)
            out.append((seq + block.tolist(), masked.copy(), logits))
            x0 = np.argmax(logits, axis=-1)
            take = reference.unmask(logits, x0, masked, owed[s], model)
            at = len(seq) - len(prompt) + np.arange(Bd)  # in the stream
            said = np.array([stream[i] if stream and 0 <= i < len(stream)
                             else t for i, t in zip(at, x0)])
            block, masked = np.where(take, said, block), masked & ~take
        if commit:
            if follow is not None:  # the block the sound run committed
                block = np.array(follow[len(out)][0][len(seq):len(seq) + Bd])
            cache, _ = forward(cache, block, commit=True)
            seq, given = seq + block.tolist(), []
    del cache
    return out


def check_forwards(engine, params, prompt, reference, follow=None,
                   stream=None) -> list:
    """The check's two parts, ``program_forwards`` behind the whole prompt
    (along ``stream``, what a round streamed behind it) and behind
    ``short_prompt``: [forwards of each] (``follow``: such a list)."""
    parts = ((prompt, stream),
             (short_prompt(prompt, engine.cfg.model.block_length), None))
    return [program_forwards(engine, params, p, reference,
                             None if follow is None else follow[i], along)
            for i, (p, along) in enumerate(parts)]


def reference_rows(ctx, params, forwards) -> list:
    """The reference's logits at every masked position of ``forwards``, in
    their order: one full forward of each sequence held (those of a length
    side by side)."""
    import jax

    by_len = {}
    for i, (held, _, _) in enumerate(forwards):
        by_len.setdefault(len(held), []).append(i)
    rows = {}
    for length, idx in by_len.items():
        logits = ctx["reference"].forward_logits(
            params, np.asarray([forwards[i][0] for i in idx], np.int32),
            ctx["config"], jax.devices()[0])
        for i, lg in zip(idx, logits):
            rows[i] = lg[length - len(forwards[i][1]):][forwards[i][1]]
    return [r for i in range(len(forwards)) for r in rows[i]]


def rms_rel(got, want) -> tuple:
    """(rms |err|, rms |logit|) over all the rows together."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return (float(np.sqrt(np.mean((got - want) ** 2))),
            float(np.sqrt(np.mean(want ** 2))))


def round_streams(engine, params, prompt) -> list:
    """[(what, budget, EOS id or None, the tokens a batcher streamed)]:
    ``prompt`` at temperature 0 in several slots at once, for the first two
    blocks behind it. The first is the whole run of both; the others end
    inside it."""
    from picotron_tpu.inference import ContinuousBatcher, Request

    Bd = engine.cfg.model.block_length
    n = 2 * Bd - len(prompt) % Bd

    def served(cases) -> list:
        out = ContinuousBatcher(engine, params, seed=0).run([
            Request(uid=what, prompt=list(prompt), max_new_tokens=budget,
                    temperature=0.0, eos_id=stop)
            for what, budget, stop in cases])
        gc.collect()  # the batcher's cache, before the next is laid
        return [case + (list(out[case[0]].tokens),) for case in cases]

    got = served([("both blocks", n, None),
                  ("a budget inside the second", n - 1, None),
                  ("a budget inside the first", 1, None)])
    whole = got[0][3]
    return got + served([
        ("an EOS inside the second", n, whole[min(n // 2, len(whole) - 1)]),
        ("both blocks, the slots taken again", n, None)])


def round_rows(prompt, forwards, streams: list, margin: float) -> list:
    """Rows of (what, err, scale, 0, ok) for the round: the whole run
    against ``forwards`` (those behind ``prompt``, which followed it), the
    draw that lies furthest under its forward's largest logit; every other
    stream against the whole run, the tokens that differ."""
    (what, owed, _, whole), *others = streams
    Bd = len(forwards[0][1])
    final = forwards[-1][0]  # the last forward's sequence holds the blocks
    worst = (0.0, 1.0)
    for (held, masked, logits), nxt in zip(forwards, forwards[1:]):
        base = len(held) - Bd
        fixed = masked & ~nxt[1] if len(nxt[0]) == len(held) else masked
        for p in np.flatnonzero(fixed):
            gap = float(logits[p].max() - logits[p][final[base + p]])
            scale = float(np.abs(logits[p]).max())
            if gap / scale >= worst[0] / worst[1]:
                worst = (gap, scale)
    rows = [(f"round, {what}: the draw furthest under its forward's largest "
             f"logit ({len(whole)} of {owed} tokens)", *worst, 0.0,
             whole == final[len(prompt):len(prompt) + owed]
             and worst[0] <= margin * worst[1])]
    for what, budget, stop, got in others:
        want = whole[:budget]
        if stop is not None:
            want = want[:want.index(stop) + 1]
        wrong = sum(a != b for a, b in zip(got, want)) \
            + abs(len(got) - len(want))
        rows.append((f"round, {what}: streamed tokens that differ",
                     float(wrong), float(len(want)), 0.0, wrong == 0))
    return rows


def logits_check(ctx, engine, params, prompt) -> tuple:
    """(ok, rows of (what, err, scale, margin, ok)): ``serve.logits_check``'s
    contract, over the denoise forwards' masked positions; behind them a
    row for their error as a whole and one for each stream of the round."""
    dtype = ctx["config"].get("torch_dtype", "bfloat16")
    streams = round_streams(engine, params, prompt)
    parts = check_forwards(engine, params, prompt, ctx["reference"],
                           stream=streams[0][3])
    forwards = sum(parts, [])
    got = [r for _, masked, logits in forwards for r in logits[masked]]
    want = reference_rows(ctx, params, forwards)
    ok, rows = serve.compare_logits(got, want, serve.TOL_LOGITS_REL[dtype])
    names = [f"forward {i} (context {len(held)}) position {p}"
             for i, (held, masked, _) in enumerate(forwards)
             for p in np.flatnonzero(masked)]
    rows = [(name,) + row[1:] for name, row in zip(names, rows)]
    err, scale = rms_rel(got, want)
    rows.append((f"all {len(got)} rows, root mean square for max", err, scale,
                 0.0, err <= TOL_RMS_REL[dtype] * scale))
    rows += round_rows(prompt, parts[0], streams, ROUND_MARGIN[dtype])
    return all(r[-1] for r in rows[len(names):]) and ok, rows


def run(ctx: dict) -> dict:
    kept, serve.logits_check = serve.logits_check, logits_check
    try:
        return serve.run(ctx)
    finally:
        serve.logits_check = kept
