"""``loop/lock_wait``: the dispatch loop waiting for ``FrontEnd._mu``, which
the HTTP handler threads take in ``submit()``; mean ms a round."""

from benchmarks import phases


def read(run):
    return phases.phase_mean_ms(run, "loop/lock_wait")
