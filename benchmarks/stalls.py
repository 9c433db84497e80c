"""Readers of the stall judge's counters (``picotron_tpu/obs/stalls.py``)
between the window's two ``GET /metrics`` scrapes: the seconds by which slow
intervals of the serving loop overran their own recent median, by ``where``,
and how far the watchdog's sleeps overran. Each family prints at 0 from the
server's start, so a delta of 0.0 says "no stall" and ``None`` says "no such
program" (the parent of the PR that added the judge: its scrape lacks the
family's name)."""

from __future__ import annotations

from benchmarks import phases

STALL_SECONDS = "picotron_stall_seconds_total"
OVERSLEEP_SECONDS = "picotron_watchdog_oversleep_seconds_total"
# the phases in which the host is blocked on a device program: the round's
# sync, and an admission's wait for its prefill's first token
DEVICE_WAIT = ("step/sync", "step/admit")


def _delta(run, family: str, **labels) -> float | None:
    after = run.get("metrics_after")
    if after is None or family not in after:
        return None
    return phases.delta(run, family, **labels)


def stall_s(run, wheres=None) -> float | None:
    """Seconds lost to slow intervals in the window, in every phase or in
    ``wheres`` alone."""
    if wheres is None:
        return _delta(run, STALL_SECONDS)
    parts = [_delta(run, STALL_SECONDS, where=w) for w in wheres]
    return None if None in parts else sum(parts)


def oversleep_s(run) -> float | None:
    """Seconds the front end's watchdog overslept in the window: near 0
    while the process is alive, the freeze's length when it stood still."""
    return _delta(run, OVERSLEEP_SECONDS)
