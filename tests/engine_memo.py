"""Toy engines kept by what they were built from, for the block test files
(``test_deepseek_v32``, ``test_granite_hybrid``, ``test_minicpm_sala``,
``test_afmoe``): a build compiles its programs anew, which is most of such a
test's time, and the tier-1 run has little of it to spare (ROADMAP D13)."""

import json


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
