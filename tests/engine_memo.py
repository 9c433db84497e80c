"""Toy engines kept by what they were built from, for the block test files
(``test_deepseek_v32``, ``test_granite_hybrid``, ``test_minicpm_sala``,
``test_afmoe``): a build compiles its programs anew, which is most of such a
test's time, and the tier-1 run has little of it to spare (ROADMAP D13).
And what a block's test drives an engine with, whatever the block (``admit``
to ``worst_rel_err``: ``test_mimo_v2`` imports them; the four older files
still carry their own copies, ROADMAP D12)."""

import json

import jax
import numpy as np


def memoized(build):
    """``build(model=None, **kw) -> (cfg, engine, params)`` as a
    ``make_engine(model=None, fresh=False, **kw)`` that hands a kept engine
    out again with nothing pending; ``fresh`` builds one of its own, for a
    test that patches what a program traces or reads the registry's
    totals."""
    kept = {}

    def make_engine(model=None, fresh=False, **kw):
        if fresh:
            return build(model, **kw)
        key = json.dumps([model, kw], sort_keys=True, default=str)
        if key not in kept:
            kept[key] = build(model, **kw)
        kept[key][1]._stats_pending.clear()
        return kept[key]

    return make_engine


def admit(engine, params, cache, prompt, slot=0):
    """The batcher's admission: chunks past ``prefill_chunk``, else the
    one-shot program and an insert. (cache, the last position's logits)."""
    if len(prompt) > engine.prefill_chunk:
        cache, last = engine.prefill_chunked(params, cache, prompt, slot)
    else:
        kv, last = engine.prefill(params, prompt)
        cache = engine.insert(cache, kv, slot, len(prompt))
    return cache, np.asarray(last, np.float32)[0]


def decode(engine, params, cache, tok, slot=0):
    toks = np.zeros(engine.slots, np.int32)
    toks[slot] = tok
    cache, _, logits = engine.decode_step(
        params, cache, toks, jax.random.PRNGKey(0),
        np.zeros(engine.slots, np.float32), np.zeros(engine.slots, np.int32),
        np.ones(engine.slots, np.float32))
    return cache, np.asarray(logits, np.float32)[slot]


def program_logits(engine, params, prompt, steps=4, cache=None, slot=0):
    """The runner's check (benchmarks/runners/serve.py::program_logits):
    the prompt through prefill, then ``steps`` greedy decode steps through
    the cache; (sequence, logits rows, cache)."""
    cache = engine.init_cache() if cache is None else cache
    cache, last = admit(engine, params, cache, prompt, slot)
    seq, got = list(prompt), [last]
    for _ in range(steps):
        seq.append(int(np.argmax(got[-1])))
        cache, logits = decode(engine, params, cache, seq[-1], slot)
        got.append(logits)
    return seq, got, cache


def worst_rel_err(got, want) -> float:
    return max(float(np.max(np.abs(g - w)) / np.max(np.abs(w)))
               for g, w in zip(got, want))
