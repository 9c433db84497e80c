"""The tokens a batcher streams from an engine that generates by diffusion
over blocks (models/sdar_moe.py, ``engine._blocks_impl``), at toy size in
float32 on the CPU with seeded weights, against the published loop with no
cache (benchmarks/reference/sdar_moe.py::generate): every block length with
every step count and both rules, prompts of every remainder and one that
holds the mask's id, a budget and an EOS that end inside a block, slots
freed and taken again, and how a round's tokens leave. The forwards' logits,
the rule, the share and the band are tests/test_sdar_moe.py's."""

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


