"""``picotron_submit_lock_wait_seconds``, window mean: what a handler thread
waits in ``FrontEnd.submit()`` for the lock the dispatch loop holds through
a step, before the batcher sees the request. The inside view of
``front.ttft_overhead_ms``; like it, no manifest entry while TTFT is not a
bounded metric."""

from benchmarks import stats


def read(run):
    return stats.window_mean_ms(run, "picotron_submit_lock_wait_seconds")
