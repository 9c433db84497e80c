"""``loop/results``: ``take_results`` to the round's last ``_deliver`` (its
lock, its JSON log line, the hand-off to the waiting handler); mean ms a
round."""

from benchmarks import phases


def read(run):
    return phases.phase_mean_ms(run, "loop/results")
