"""Mean of ``picotron_queue_wait_seconds`` over the window: submit to
admission into a slot."""

from benchmarks import stats


def read(run):
    return stats.window_mean_ms(run, "picotron_queue_wait_seconds")
