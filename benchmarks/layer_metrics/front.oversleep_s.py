"""Seconds the front end's watchdog thread overslept in the window
(``picotron_watchdog_oversleep_seconds_total``): it only sleeps, so what it
overslept is how long the whole process stood still, whatever the dispatch
loop was waiting for."""

from benchmarks import stalls


def read(run):
    return stalls.oversleep_s(run)
