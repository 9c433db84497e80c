"""Seconds of the window lost to slow intervals of the dispatch loop: for
every phase of every round (``loop/lock_wait``, ``step/plan`` .. ``step/deliver``,
``loop/results``) judged slow against its own recent median, the duration
less that median (``picotron_stall_seconds_total``, every ``where``). Over
``window_s`` it is the share of tokens/s a stall took."""

from benchmarks import stalls


def read(run):
    return stalls.stall_s(run)
