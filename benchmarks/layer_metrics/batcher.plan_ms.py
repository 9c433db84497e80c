"""``step/plan`` + ``step/issue``: a round's host work from ``step()``'s entry
to the program enqueued, less admission: expiry, rebalance, budgets, lanes,
keys, the speculation plan, the engine call; mean ms a round."""

from benchmarks import phases


def read(run):
    return phases.phase_mean_ms(run, "step/plan", "step/issue")
