"""The stall judge: every named interval against its own recent past.

A mean over a window cannot see one slow round among five hundred, and a
watchdog that speaks after 60 s cannot see three (docs/OBSERVABILITY.md
"Stalls"). ``StallWatch`` is told ``(where, key, seconds)`` for each
interval of a loop's tiling and keeps, for every ``(where, key)``, the
last ``HISTORY`` durations; their median is the interval's reference. An
interval is SLOW when it has at least ``MIN_HISTORY`` predecessors and ran
more than ``SLOW_RATIO`` times the reference AND at least ``SLOW_EXCESS_S``
over it. The excess (duration less reference) is counted where the two
scrapes of a window see it:

- ``picotron_stall_seconds_total{where}`` / ``picotron_stalls_total{where}``
  (one child a registered ``where``, so each prints at 0 from the start),
- ``picotron_watchdog_oversleep_seconds_total``: how far a thread that
  only sleeps overslept (``OVERSLEEP_FLOOR_S`` and more at a time); a
  process frozen from outside reads the freeze,
- ``picotron_gc_pause_seconds_total{generation}`` on the process registry.

and a RECORD of the slow interval is kept (the ``TABLE`` largest since
start, ``stats()`` / ``/statz`` ``stalls``; each also waits in
``take_slow()`` for the front end to log as a ``slow_interval`` event): the
round's phases and parts, what the caller knows of the round, and the
host's evidence over it (``_evidence``), sampled once a round and
differenced. The ring's spans of the slow round are pinned
(``SpanTracer.pin``) so ``/tracez`` still has them when the ring has turned
over.

``where`` is the phase; ``key`` is what legitimately sets its length (the
prefill work a ``step/admit`` dispatched, the kind of round a
``step/issue`` / ``step/sync`` ran), so a long admission is held against
long admissions. The serving loop feeds it from the two places that end a
phase and hold its seconds already (``RoundPhases.close``, the exit of
``Obs.phase``); nothing here reads a clock on the path of an interval that
is not slow but the three reads of ``_sample``, once a round.
"""

from __future__ import annotations

import gc
import resource
import threading
import time
from collections import deque
from typing import Optional

# Durations kept a (where, key): long enough that a burst of slow rounds
# does not become the reference, short enough to follow a context that
# grows (the long-context cells' sync/wait climbs a key block at a time).
HISTORY = 64
# No verdict before a key has this many predecessors: the first rounds of
# a program hold its compile and the allocator's first touch.
MIN_HISTORY = 8
# Slow is more than this many times the median: a round's legitimate
# spread (a fuller batch, a longer context inside one key) stays under 2 x.
SLOW_RATIO = 3.0
# ... AND at least this far over it: 3 x of a 0.2 ms phase is noise, and
# no sighting in the ledger is under 2 s. Nothing shorter is ever judged.
SLOW_EXCESS_S = 0.1
# Records kept: the largest excesses since start.
TABLE = 16
# A watchdog sleep that overran by less is the scheduler and the
# interpreter's lock (a switch interval is 5 ms), not a freeze: unfloored, a
# quiet 30 s window's 120 naps summed to 0.06-0.08 s on the chip's host.
OVERSLEEP_FLOOR_S = 0.01

SECONDS_TOTAL = "picotron_stall_seconds_total"
COUNT_TOTAL = "picotron_stalls_total"
OVERSLEEP_TOTAL = "picotron_watchdog_oversleep_seconds_total"
GC_PAUSE_TOTAL = "picotron_gc_pause_seconds_total"

_RUSAGE_WHO = getattr(resource, "RUSAGE_THREAD", resource.RUSAGE_SELF)

# seconds the collector has run in this process; [0] so the callback writes
# without a ``global``
_gc_seconds = [0.0]
_gc_installed = False


def install_gc_pause_counter(registry) -> None:
    """Count the collector's pauses into ``registry`` (the process-wide
    one: a collection stops every thread of every engine), a generation.
    Once a process; later calls do nothing."""
    global _gc_installed
    if _gc_installed:
        return
    _gc_installed = True
    children = [registry.counter(
        GC_PAUSE_TOTAL, "seconds the cyclic collector held every thread",
        generation=str(g)) for g in range(3)]
    t0 = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            t0[0] = time.perf_counter()
        else:
            dt = time.perf_counter() - t0[0]
            _gc_seconds[0] += dt
            children[min(info.get("generation", 2), 2)].inc(dt)

    gc.callbacks.append(on_gc)


def admit_key(dispatches: int, rows: int) -> str:
    """What sets a ``step/admit``'s length: how many prefill programs it
    dispatched and how many rows they ran, each rounded up to a power of
    two as the engine's own buckets are. Short prompts cost a dispatch
    each (the weights are read once a program) and long ones their rows,
    so inside one key neither moves the time by 2 x."""
    if dispatches <= 0:
        return "none"
    return f"{_pow2(dispatches)}x{max(_pow2(rows), 16)}"


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class StallWatch:
    """See the module docstring. Fed by one thread at a time (the loop's);
    ``stats()`` and ``oversleep()`` may come from others."""

    enabled = True

    def __init__(self, registry, tracer):
        self._registry = registry
        self._tracer = tracer
        self._history: dict = {}  # (where, key) -> deque of seconds
        self._seconds: dict = {}  # where -> Counter, the registered ones
        self._counts: dict = {}
        self._oversleep = None    # the watchdog's counter, once registered
        self._oversleep_s = 0.0   # its value, for the evidence (one writer)
        self._parts: dict = {}    # the open round's parts
        self._round = 0           # the last round closed
        self._round_t0 = 0.0      # ... and where it began, ring clock
        self._base = None         # the host's evidence at the last close
        self._base_t = 0.0        # ... and the ring's clock then
        self._mu = threading.Lock()  # the table and the unsent records
        self._table: list = []
        self._unsent: deque = deque(maxlen=TABLE)

    # ---- registration ------------------------------------------------------

    def register(self, *wheres: str) -> None:
        """These intervals are judged; each prints at 0 from now on. An
        interval under any other name is not (``loop/idle``: waiting for
        work is no stall)."""
        for where in wheres:
            self._seconds[where] = self._registry.counter(
                SECONDS_TOTAL, "seconds by which slow intervals of the "
                "serving loop overran their own recent median", where=where)
            self._counts[where] = self._registry.counter(
                COUNT_TOTAL, "intervals of the serving loop judged slow",
                where=where)

    def register_watchdog(self) -> None:
        self._oversleep = self._registry.counter(
            OVERSLEEP_TOTAL, "seconds the watchdog's sleeps overran: a "
            "thread that only sleeps, so what froze the whole process")

    def oversleep(self, seconds: float) -> None:
        """The watchdog slept this long past what it asked for."""
        if seconds >= OVERSLEEP_FLOOR_S and self._oversleep is not None:
            self._oversleep.inc(seconds)
            self._oversleep_s += seconds

    # ---- the judge ---------------------------------------------------------

    def judge(self, where: str, key: str, seconds: float) -> Optional[tuple]:
        """(reference, excess) if this interval is slow, else None; either
        way it joins its key's history."""
        past = self._history.get((where, key))
        if past is None:
            past = self._history[(where, key)] = deque(maxlen=HISTORY)
        verdict = None
        # an interval under the floor cannot be slow whatever its
        # reference: the common case costs no median
        if seconds >= SLOW_EXCESS_S and len(past) >= MIN_HISTORY:
            ordered = sorted(past)
            mid = len(ordered) // 2
            ref = (ordered[mid] if len(ordered) % 2
                   else 0.5 * (ordered[mid - 1] + ordered[mid]))
            if seconds > SLOW_RATIO * ref and seconds - ref >= SLOW_EXCESS_S:
                verdict = (ref, seconds - ref)
        past.append(seconds)
        return verdict

    def _count(self, where: str, excess: float) -> None:
        self._seconds[where].inc(excess)
        self._counts[where].inc()

    def part(self, span) -> None:
        """A part of the open round ended (``Obs.part``)."""
        if span.t1 is not None:
            self._parts[span.name] = (self._parts.get(span.name, 0.0)
                                      + span.t1 - span.t0)

    def interval(self, span) -> None:
        """A scoped phase outside the round ended (``Obs.phase``: the
        loop's ``loop/lock_wait`` and ``loop/results``)."""
        where = span.name
        if where not in self._seconds or span.t1 is None:
            return
        seconds = span.t1 - span.t0
        verdict = self.judge(where, "", seconds)
        if verdict is None:
            return
        self._count(where, verdict[1])
        # the results belong to the round just closed; a wait for the lock
        # comes before the next one has begun
        t0 = self._round_t0 if where == "loop/results" else span.t0
        self._keep([(where, "", seconds) + verdict], span.t0, span.t1,
                   pin_from=t0, phases={where: seconds}, parts={},
                   facts=None, host=self._evidence(span.t1, keep=False))

    def round_closed(self, seq: int, seconds: dict, keys: dict, t0: float,
                     t1: float, facts=None) -> None:
        """Round ``seq`` ended (``RoundPhases.close``): judge each of its
        phases under its key. ``facts`` is called for what the caller
        knows of the round, and only if a phase was slow."""
        slow = []
        for where, s in seconds.items():
            if where not in self._seconds:
                continue
            key = keys.get(where, "")
            verdict = self.judge(where, key, s)
            if verdict is not None:
                self._count(where, verdict[1])
                slow.append((where, key, s) + verdict)
        parts, self._parts = self._parts, {}
        host = self._evidence(t1, keep=True)
        self._round, self._round_t0 = seq, t0
        if slow:
            self._keep(slow, t0, t1, pin_from=t0, phases=dict(seconds),
                       parts=parts, facts=facts, host=host)

    # ---- the record --------------------------------------------------------

    def _sample(self) -> tuple:
        ru = resource.getrusage(_RUSAGE_WHO)
        return (time.thread_time(), time.process_time(), ru.ru_nivcsw,
                ru.ru_majflt, _gc_seconds[0], self._oversleep_s)

    def _evidence(self, now: float, keep: bool) -> tuple:
        """The host's evidence since the last round closed, raw (``_host``
        words it, for a record): sampled now and, at a round's close,
        kept as what the next one is differenced against."""
        since = (self._base, self._sample(), now - self._base_t)
        if keep:
            self._base, self._base_t = since[1], now
        return since

    @staticmethod
    def _host(base, sample, wall: float) -> dict:
        """What the host did over ``wall_s`` of the ring's clock: the
        calling (loop) thread's and the process's CPU seconds, the
        thread's involuntary context switches and major faults, the
        collector's seconds, the watchdog's oversleep. Both CPU clocks
        near zero with the watchdog oversleeping: the process was frozen
        from outside. Process CPU near the wall time: a thread held the
        interpreter. All quiet and ``sync/wait`` long: the device or its
        runtime."""
        if base is None:  # the first round: nothing to difference against
            return {}
        d = [b - a for a, b in zip(base, sample)]
        return {"wall_s": _r(wall), "thread_cpu_s": _r(d[0]),
                "process_cpu_s": _r(d[1]), "involuntary_switches": d[2],
                "major_faults": d[3], "gc_s": _r(d[4]),
                "oversleep_s": _r(d[5])}

    def _keep(self, slow: list, t0: float, t1: float, pin_from: float,
              phases: dict, parts: dict, facts, host: tuple) -> None:
        known = facts() if facts is not None else {}
        host = self._host(*host)
        unix_t0 = time.time() - (t1 - t0)
        worst = max(slow, key=lambda v: v[4])
        self._tracer.pin(pin_from, t1, stall_round=self._round,
                         stall_where=worst[0])
        for where, key, seconds, ref, excess in slow:
            rec = {"where": where, "key": key, "round": self._round,
                   "t0": _r(t0), "unix_t0": round(unix_t0, 3),
                   "duration_s": _r(seconds), "reference_s": _r(ref),
                   "excess_s": _r(excess),
                   "phases": {k: _r(v) for k, v in phases.items()},
                   "parts": {k: _r(v) for k, v in parts.items()},
                   **known, "host": host}
            with self._mu:
                self._unsent.append(rec)
                self._table.append(rec)
                self._table.sort(key=lambda r: -r["excess_s"])
                del self._table[TABLE:]

    def take_slow(self) -> list:
        """The records not yet handed out (the front end logs each as one
        ``slow_interval`` event, outside its lock)."""
        if not self._unsent:
            return []
        with self._mu:
            out = list(self._unsent)
            self._unsent.clear()
        return out

    def stats(self) -> dict:
        """``/statz`` ``stalls``: the totals a ``where`` and the table."""
        with self._mu:
            table = list(self._table)
        return {"seconds": {w: _r(c.value) for w, c in self._seconds.items()},
                "count": {w: int(c.value) for w, c in self._counts.items()},
                "oversleep_s": _r(self._oversleep_s),
                "slowest": table}


class NullStallWatch:
    """``obs.enabled: false``: no history, no counters, no record."""

    enabled = False

    def register(self, *wheres) -> None:
        pass

    def register_watchdog(self) -> None:
        pass

    def oversleep(self, seconds) -> None:
        pass

    def judge(self, where, key, seconds) -> None:
        return None

    def part(self, span) -> None:
        pass

    def interval(self, span) -> None:
        pass

    def round_closed(self, seq, seconds, keys, t0, t1, facts=None) -> None:
        pass

    def take_slow(self) -> list:
        return []

    def stats(self) -> dict:
        return {}


def _r(v: Optional[float]) -> Optional[float]:
    return None if v is None else round(v, 6)
