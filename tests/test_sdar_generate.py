"""The tokens a batcher streams from an engine that generates by diffusion
over blocks (models/sdar_moe.py, ``engine._blocks_impl``), at toy size in
float32 on the CPU with seeded weights, against the published loop with no
cache (benchmarks/reference/sdar_moe.py::generate): every block length with
every step count and both rules, prompts of every remainder and one that
holds the mask's id, a budget and an EOS that end inside a block, slots
freed and taken again, and how a round's tokens leave. The forwards' logits,
the rule, the share and the band are tests/test_sdar_moe.py's."""

from functools import lru_cache

import numpy as np
import pytest
from test_sdar_moe import MASK, TOY, make_engine, ref, tokens

from picotron_tpu.inference import ContinuousBatcher, Request, sampling


def serve(engine, params, requests) -> dict:
    out = ContinuousBatcher(engine, params, seed=0).run(requests)
    return {uid: r.tokens for uid, r in out.items()}


# every block length with every step count {1, Bd / 2, Bd}, the two rules
# turn about (and both at the cell's own schedule): an engine a schedule
STATIC, DYNAMIC = sampling.REMASKING
SCHEDULES = [(2, 1, STATIC), (2, 2, DYNAMIC), (4, 1, DYNAMIC),
             (4, 2, STATIC), (4, 4, DYNAMIC), (4, 4, STATIC),
             (8, 1, STATIC), (8, 4, DYNAMIC), (8, 8, STATIC)]


@pytest.mark.parametrize("bd,steps,rule", SCHEDULES + [
    (1, 1, STATIC)])  # the autoregressive limit
def test_streamed_tokens_are_the_published_loops(bd, steps, rule):
    """Prompts of every remainder ``0 .. Bd - 1`` behind two whole chunks
    (the lengths chosen so that the reference's forwards share their
    shapes), more requests than slots (a slot is freed and taken again: the
    stale provisional rows of its last occupant lie beyond the new length),
    a budget that ends inside a block, and a prompt that holds the mask's
    id, among its whole blocks and in its remainder."""
    model = dict(block_length=bd, denoising_steps=steps, remasking=rule)
    _, engine, params = make_engine(model, decode_block_len=max(8, bd))
    prompts = [tokens(10 * bd + r, 32 + r) for r in range(bd)]
    prompts[-1][5] = prompts[-1][-1] = MASK
    n_new = bd + 3
    got = serve(engine, params, [
        Request(uid=str(i), prompt=p, max_new_tokens=n_new)
        for i, p in enumerate(prompts)])
    for i, p in enumerate(prompts):
        want = ref.generate(params, p, n_new, {**TOY, **model})
        assert got[str(i)] == want, (i, len(p))


def test_eos_inside_a_block_ends_the_stream_there():
    _, engine, params = make_engine()
    prompt = tokens(5, 34)
    free = ref.generate(params, prompt, 12, TOY)
    # a token whose first appearance is neither a block's first nor last
    at = next(i for i, t in enumerate(free)
              if free.index(t) == i and (34 + i) % 4 in (1, 2, 3)
              and (i + 34) % 4 != 3)
    eos = free[at]
    want = ref.generate(params, prompt, 12, TOY, eos_id=eos)
    assert want == free[:at + 1] and len(want) < 12
    out = ContinuousBatcher(engine, params, seed=0).run(
        [Request(uid="a", prompt=prompt, max_new_tokens=12, eos_id=eos),
         Request(uid="b", prompt=prompt, max_new_tokens=12)])
    assert out["a"].tokens == want and out["a"].finish_reason == "eos"
    assert out["b"].tokens == free and out["b"].finish_reason == "length"


def test_tokens_leave_a_round_together_and_every_one_is_streamed():
    _, engine, params = make_engine()
    seen = []
    batcher = ContinuousBatcher(
        engine, params, seed=0,
        on_tokens=lambda uid, toks: seen.append(list(toks)))
    out = batcher.run([Request(uid="a", prompt=tokens(6, 18),
                               max_new_tokens=21)])
    # 2 given positions: the first round commits 2 + 4 new tokens, then 8 a
    # round of two blocks, the last block cut at the budget
    assert [len(t) for t in seen] == [6, 8, 7]
    assert sum(seen, []) == out["a"].tokens and len(out["a"].tokens) == 21




# ---- a finished block is committed by the forward that starts the next ------
# (ISSUE 66: ``engine._fused_forward``; the round's last block waits, and the
# batcher hands it back with the slot's next round)


def _first_at(free: list, lo: int, hi: int):
    """The index in ``lo .. hi`` of a token of ``free`` that appears there
    first, or None."""
    return next((i for i in range(lo, hi) if free.index(free[i]) == i), None)


@lru_cache(maxsize=None)
def mixed_batch() -> dict:
    """One batcher's run over slots that differ in state, three slots and
    eight requests, so that a slot is taken again while its neighbours hold
    waiting blocks: {uid: (tokens streamed, tokens wanted, reason wanted)}.
    Rounds of two blocks of 4, a window of 128."""
    _, engine, params = make_engine()
    gen = lambda p, n, eos=None: ref.generate(params, p, n, TOY, eos_id=eos)
    cases = {}
    # a stream of four rounds: a block waits between each two, and the
    # budget ends inside the last round's second block
    cases["waiting blocks over four rounds"] = (tokens(40, 16), 30, None)
    # fresh admissions beside it, with a given remainder and without; the
    # budget ends inside a round's first block
    cases["given remainder, budget in a first block"] = (
        tokens(41, 18), 6 + 8 + 3, None)
    cases["no remainder, two whole rounds"] = (tokens(42, 20), 16, None)
    # an EOS inside the second round's first block, and inside its second
    for what, lo, seed in (("first", 8, 50), ("second", 12, 60)):
        prompt, at = next(
            (p, at) for p in (tokens(seed + i, 12) for i in range(20))
            for at in [_first_at(gen(p, 16), lo + 1, lo + 3)]
            if at is not None)
        cases[f"EOS in a round's {what} block"] = (
            prompt, 24, gen(prompt, 16)[at])
    # the window's end: 128 - 110 = 18 tokens whatever the budget, the last
    # block the window's last
    cases["the window's end"] = (tokens(43, 110), 40, None)
    # and two that take a slot whose last occupant left on a round's last
    # token, its last block just noted as waiting
    cases["a slot taken again, a remainder"] = (tokens(44, 9), 9, None)
    cases["a slot taken again, whole blocks"] = (tokens(45, 8), 12, None)
    batcher = ContinuousBatcher(engine, params, seed=0)
    out = batcher.run([
        Request(uid=uid, prompt=p, max_new_tokens=n, eos_id=eos)
        for uid, (p, n, eos) in cases.items()])
    assert (batcher._waiting == -1).all()  # every stream ended: none owed
    got = {}
    for uid, (p, n, eos) in cases.items():
        want = gen(p, min(n, 128 - len(p)), eos)
        reason = "eos" if eos is not None and want[-1] == eos else "length"
        got[uid] = (out[uid].tokens, out[uid].finish_reason, want, reason)
    return got


@pytest.mark.parametrize("uid", [
    "waiting blocks over four rounds",
    "given remainder, budget in a first block",
    "no remainder, two whole rounds", "EOS in a round's first block",
    "EOS in a round's second block", "the window's end",
    "a slot taken again, a remainder", "a slot taken again, whole blocks"])
def test_slots_that_differ_in_state_stream_the_published_loops_tokens(uid):
    """The fused round program with, in one batch, a slot whose block waits,
    fresh admissions with and without a given remainder, streams that end
    by an EOS inside a round's first and second block, by the budget, and
    at the window's end, and slots released and taken again: every stream
    is the published loop's, token for token."""
    tokens_got, reason_got, want, reason = mixed_batch()[uid]
    assert tokens_got == want and reason_got == reason
    if uid.startswith("EOS"):
        at = len(want) - 1
        assert (at % 8 >= 4) == ("second" in uid) and at >= 8


def test_a_released_slot_does_not_keep_its_occupants_waiting_block():
    """A stream that ends on a round's last token leaves a whole block
    noted as waiting; the release clears it, so the slot's next occupant
    starts clean, and so does the next occupant of a slot whose stream
    timed out between two rounds, its block waiting."""
    _, engine, params = make_engine()
    now = [0.0]
    batcher = ContinuousBatcher(engine, params, seed=0, clock=lambda: now[0])
    first, second = tokens(70, 8), tokens(71, 10)
    gen = lambda p, n: ref.generate(params, p, n, TOY)
    assert batcher.run([Request(uid="a", prompt=first, max_new_tokens=8)])[
        "a"].tokens == gen(first, 8)
    assert batcher.run([Request(uid="b", prompt=second, max_new_tokens=9)])[
        "b"].tokens == gen(second, 9)
    batcher.submit(Request(uid="c", prompt=first, max_new_tokens=40,
                           timeout_s=1.0))
    batcher.step()
    slot = next(i for i, s in enumerate(batcher._slots) if s is not None)
    assert (batcher._waiting[slot] >= 0).all()  # its second block waits
    now[0] = 10.0
    batcher.step()
    assert batcher._slots[slot] is None and (batcher._waiting == -1).all()
    out = batcher.run([Request(uid="d", prompt=second, max_new_tokens=9)])
    assert out["c"].finish_reason == "timeout"
    assert out["c"].tokens == gen(first, 8)
    assert out["d"].tokens == gen(second, 9)


@pytest.mark.parametrize("rounds", [1, 3])
def test_the_cache_behind_fused_rounds_is_the_unfused_forwards(rounds):
    """The cache after ``rounds`` rounds, the waiting block committed, holds
    for every live row the K/V of the same blocks forwarded one at a time
    (``engine.block_forward``: a block's denoise forwards, then ``commit=
    True``), the lengths equal and the tokens the same: three slots, a
    remainder of 0, 1 and 3."""
    _, engine, params = make_engine()
    Bd, steps = TOY["block_length"], TOY["denoising_steps"]
    prompts = [tokens(80, 16), tokens(81, 9), tokens(82, 23)]
    requests = lambda: [Request(uid=str(i), prompt=p, max_new_tokens=64)
                        for i, p in enumerate(prompts)]
    streamed = {str(i): [] for i in range(3)}
    fused = ContinuousBatcher(
        engine, params, seed=0,
        on_tokens=lambda uid, toks: streamed[uid].extend(toks))
    for r in requests():
        fused.submit(r)
    for _ in range(rounds):
        fused.step()
    slot_of = {s.req.uid: i for i, s in enumerate(fused._slots)}
    owed = (fused._waiting >= 0).all(axis=1)
    assert owed.all()
    cache, _ = engine.block_forward(params, fused._cache, fused._waiting,
                                    owed, commit=True)
    fused._cache = None

    plain = ContinuousBatcher(engine, params, seed=0)
    for r in requests():
        plain.submit(r)
    plain._admit()
    assert {s.req.uid: i for i, s in enumerate(plain._slots)} == slot_of
    want, plain._cache = plain._cache, None
    owed_n = ref.transfer_counts(Bd, steps)
    given = [plain._given[i, :plain._given_n[i]].tolist() for i in range(3)]
    live = np.ones(3, bool)
    for _ in range(2 * rounds):
        block = np.array([g + [MASK] * (Bd - len(g)) for g in given])
        masked = np.array([np.arange(Bd) >= len(g) for g in given])
        for s in range(steps):
            want, logits = engine.block_forward(params, want, block, live)
            logits = np.asarray(logits, np.float32)
            for i in range(3):
                x0 = np.argmax(logits[i], axis=-1)
                take = ref.unmask(logits[i], x0, masked[i], owed_n[s], TOY)
                block[i] = np.where(take, x0, block[i])
                masked[i] &= ~take
        assert not masked.any()
        want, _ = engine.block_forward(params, want, block, live, commit=True)
        for uid, i in slot_of.items():
            n = Bd - len(given[i])
            assert streamed[uid][:n] == block[i, Bd - n:].tolist()
            del streamed[uid][:n]
        given = [[], [], []]
    assert not any(streamed.values())
    lengths = np.asarray(want["lengths"])
    assert (np.asarray(cache["lengths"]) == lengths).all()
    assert lengths.tolist() == [len(p) // Bd * Bd + 8 * rounds
                                for p in prompts]
    for leaf in ("k", "v"):
        a, b = (np.asarray(c[leaf], np.float32) for c in (cache, want))
        for i, n in enumerate(lengths):
            err = np.abs(a[:, i, :n] - b[:, i, :n]).max()
            assert err <= 1e-3 * np.abs(b[:, i, :n]).max(), (leaf, i, err)
