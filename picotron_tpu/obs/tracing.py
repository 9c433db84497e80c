"""Span tracer: begin/end spans with parent links in a bounded ring.

The per-request / per-step timeline complement of the metrics registry
(docs/OBSERVABILITY.md). Spans are cheap host-side records — name, wall
window, thread, parent id, small args dict — appended to a bounded ring
when they END (an unfinished span costs nothing but its object). The ring
is the export surface: ``chrome_trace()`` renders the retained spans as
Chrome-trace/Perfetto ``traceEvents`` JSON (``tools/trace_dump.py``
validates and queries it; ``GET /tracez`` on the serving front end dumps
it live), with each event's ``args`` carrying ``id``/``parent`` so a
request's whole chain — queue wait -> prefill -> every dispatch ->
delivery — reads as one parented tree.

Three record styles:

- ``with tracer.span("prefill", parent=root, prompt_tokens=n):`` — the
  common scoped form;
- ``begin()`` / ``end()`` — for windows that open and close in different
  call frames (a request's root span lives from submit to finish);
- ``record(name, t0, t1, parent=...)`` — retroactive: one engine dispatch
  serves many slots, so the batcher mirrors the dispatch window into one
  child span PER REQUEST after the fact, which is what makes every
  request's chain complete without multi-parent events.

``instant()`` records zero-duration marks (trace-time collective logs
from ``comm_trace``).

``pin(t0, t1, **label)`` copies the ring's spans that ended in a window to
a bounded list beside the ring (the stall judge's slow rounds,
``obs/stalls.py``): ``chrome_trace()`` renders them with the ring's own
after the ring has turned over.

While a ``jax.profiler`` capture is open (``obs.profiler.ProfileCapture``
sets the module flag below) a SCOPED span is written twice: into the ring
as always, and as a ``jax.profiler.TraceAnnotation`` into the capture's
``.xplane.pb``, on the clock the device events are on, so a host span can
be laid against a device gap. Its name there is ``pt:<name>`` when the
span was entered on a thread that claimed itself a loop
(``claim_loop_thread``: the serving dispatch loop, the training loop) and
``pt.req:<name>`` on any other thread (HTTP handlers), so a reduction that
names device gaps by the innermost host span reads the loop's phases only.
Args stay on the ring span: annotation kwargs would split one phase into
many names. ``begin()``/``end()``/``record()`` spans reach the ring only;
``anchor()`` (one at a capture's start, one at its stop) carries the ring
clock's reading into the capture so they can be shifted onto its clock
afterwards: ``trace_ns = ring_s * 1e9 + (anchor.start_ns - ring_ns)``.
With no capture open the cost is one global read per scoped span.

Thread-safety: one leaf lock guards the id counter and ring; nothing else
is shared. The clock is ``time.monotonic`` (one timebase across threads);
timestamps are exported in microseconds as Chrome expects.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Optional

DEFAULT_RING = 4096
# Slow rounds whose spans are kept beside the ring (``SpanTracer.pin``): as
# many as the stall judge keeps records of (``obs/stalls.py::TABLE``).
PINNED_ROUNDS = 16

LOOP_PREFIX = "pt:"      # scoped spans of a claimed loop thread
REQ_PREFIX = "pt.req:"   # scoped spans of any other thread
ANCHOR_NAME = "pt.anchor"

# True while ProfileCapture holds a jax.profiler capture open. Process-wide
# because the profiler is: jax refuses a second concurrent trace.
_capture_open = False


def set_capture_open(flag: bool) -> None:
    global _capture_open
    _capture_open = bool(flag)


def capture_open() -> bool:
    return _capture_open


class Span:
    """One timed window. ``t1 is None`` until ended/recorded."""

    __slots__ = ("name", "span_id", "parent_id", "t0", "t1", "tid", "args")

    def __init__(self, name: str, span_id: int, parent_id: Optional[int],
                 t0: float, tid: int, args: dict):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = t0
        self.t1: Optional[float] = None
        self.tid = tid
        self.args = args

    @property
    def duration_s(self) -> Optional[float]:
        return None if self.t1 is None else self.t1 - self.t0


class _NullSpan(Span):
    __slots__ = ()

    def __init__(self):
        super().__init__("", 0, None, 0.0, 0, {})


NULL_SPAN = _NullSpan()


def _parent_id(parent) -> Optional[int]:
    if parent is None:
        return None
    pid = parent.span_id if isinstance(parent, Span) else int(parent)
    return pid or None  # the null span's id 0 means "no parent"


class SpanTracer:
    """Bounded ring of finished spans (oldest dropped past ``ring``)."""

    def __init__(self, ring: int = DEFAULT_RING, clock=time.monotonic):
        self._mu = threading.Lock()
        self._clock = clock
        self._next_id = 1
        self._ring: deque = deque(maxlen=max(1, int(ring)))
        # (label, spans) of the windows pinned: copies out of the ring that
        # outlive its turning over
        self._pinned: deque = deque(maxlen=PINNED_ROUNDS)
        self._loop_threads: set = set()  # idents that claimed "pt:"

    @property
    def enabled(self) -> bool:
        return True

    def resize(self, ring: int) -> None:
        """Grow (never shrink) the ring — config-driven sizing of the
        shared process tracer without discarding retained spans."""
        ring = int(ring)
        with self._mu:
            if ring > (self._ring.maxlen or 0):
                self._ring = deque(self._ring, maxlen=ring)

    # ---- record surface ----------------------------------------------------

    def begin(self, name: str, parent=None, **args) -> Span:
        with self._mu:
            sid = self._next_id
            self._next_id += 1
        return Span(name, sid, _parent_id(parent), self._clock(),
                    threading.get_ident(), args)

    def end(self, span: Span, **args) -> Span:
        if span.span_id == 0:  # null span
            return span
        span.t1 = self._clock()
        if args:
            span.args = {**span.args, **args}
        with self._mu:
            self._ring.append(span)
        return span

    class _Scoped:
        __slots__ = ("_tracer", "_span", "_annotation")

        def __init__(self, tracer: "SpanTracer", span: Span):
            self._tracer = tracer
            self._span = span
            self._annotation = None

        def __enter__(self) -> Span:
            if _capture_open and self._span.span_id:  # not the null span
                self._annotation = self._tracer._annotate(self._span.name)
            return self._span

        def __exit__(self, exc_type, exc, tb) -> None:
            if self._annotation is not None:
                self._annotation.__exit__(exc_type, exc, tb)
            if exc_type is not None:
                self._span.args = {**self._span.args,
                                   "error": exc_type.__name__}
            self._tracer.end(self._span)

    def span(self, name: str, parent=None, **args) -> "_Scoped":
        return self._Scoped(self, self.begin(name, parent=parent, **args))

    def record(self, name: str, t0: float, t1: float, parent=None,
               **args) -> Span:
        """Retroactively record a finished window."""
        s = self.begin(name, parent=parent, **args)
        s.t0 = t0
        s.t1 = t1
        with self._mu:
            self._ring.append(s)
        return s

    def instant(self, name: str, **args) -> Span:
        t = self._clock()
        return self.record(name, t, t, **args)

    def pin(self, t0: float, t1: float, **label) -> int:
        """Keep the ring's spans that ENDED in ``[t0, t1]`` (a slow round's
        phases and parts, its ``dispatch/*`` with the request chains'
        children of it; an overlapped dispatch began a round earlier)
        beside the ring, the last ``PINNED_ROUNDS`` windows of them:
        ``chrome_trace()`` renders them with the ring's own, each with
        ``label`` among its args, after the ring has dropped them. Returns
        how many it kept."""
        with self._mu:
            ring = list(self._ring)
        spans = [s for s in ring if t0 <= s.t1 <= t1]
        with self._mu:
            self._pinned.append((label, spans))
        return len(spans)

    def adopt(self, parent: Span, child: Span) -> None:
        """Make ``child``, a span that ended before ``parent`` could exist
        (a wait that came before the request was known), the first link of
        ``parent``'s chain: ``parent`` begins where ``child`` began."""
        parent.t0 = child.t0
        child.parent_id = parent.span_id

    # ---- the profiler's trace ----------------------------------------------

    def claim_loop_thread(self) -> None:
        """The calling thread runs a dispatch or training loop: its scoped
        spans are named ``pt:`` in a capture. Release when the loop ends
        (thread idents are reused)."""
        with self._mu:
            self._loop_threads.add(threading.get_ident())

    def release_loop_thread(self) -> None:
        with self._mu:
            self._loop_threads.discard(threading.get_ident())

    def _annotate(self, name: str):
        """An entered ``TraceAnnotation`` twin of a scoped span."""
        import jax

        prefix = (LOOP_PREFIX if threading.get_ident() in self._loop_threads
                  else REQ_PREFIX)
        annotation = jax.profiler.TraceAnnotation(prefix + name)
        annotation.__enter__()
        return annotation

    def anchor(self) -> None:
        """One ``pt.anchor`` annotation whose ``ring_ns`` stat is this
        ring's clock at the annotation's start (module docstring)."""
        import jax

        with jax.profiler.TraceAnnotation(
                ANCHOR_NAME, ring_ns=int(self._clock() * 1e9)):
            pass

    # ---- read side ---------------------------------------------------------

    def spans(self) -> list:
        with self._mu:
            return list(self._ring)

    def pinned(self) -> list:
        """[(label, spans)] of the windows ``pin`` kept, oldest first."""
        with self._mu:
            return list(self._pinned)

    def clear(self) -> None:
        with self._mu:
            self._ring.clear()
            self._pinned.clear()

    def chrome_trace(self) -> dict:
        """Chrome-trace JSON ("traceEvents" array format): one complete
        ("X") event per span — instants (t0 == t1) render as "i" — with
        ``args.id``/``args.parent`` carrying the chain links. A pinned
        span (``pin``) is rendered once, in the ring or out of it, with its
        window's label among its args."""
        pid = os.getpid()
        events = []
        ring = self.spans()
        seen = {s.span_id for s in ring}
        labels, gone = {}, []  # pinned spans' labels; those the ring lost
        for label, spans in self.pinned():
            for s in spans:
                labels[s.span_id] = label
                if s.span_id not in seen:
                    seen.add(s.span_id)
                    gone.append(s)
        for s in gone + ring:
            args = {"id": s.span_id}
            if s.parent_id:
                args["parent"] = s.parent_id
            args.update(s.args)
            args.update(labels.get(s.span_id, ()))
            ev = {"name": s.name, "cat": "picotron", "pid": pid,
                  "tid": s.tid, "ts": round(s.t0 * 1e6, 3), "args": args}
            if s.t1 is not None and s.t1 > s.t0:
                ev["ph"] = "X"
                ev["dur"] = round((s.t1 - s.t0) * 1e6, 3)
            else:
                ev["ph"] = "i"
                ev["s"] = "p"
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump_chrome(self, path: str) -> None:
        import json

        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)


class NullTracer(SpanTracer):
    """``obs.enabled: false``: the whole record surface no-ops and hands
    back the shared null span (parenting off it is a no-op too)."""

    def __init__(self):
        super().__init__(ring=1)

    @property
    def enabled(self) -> bool:
        return False

    def begin(self, name, parent=None, **args) -> Span:
        return NULL_SPAN

    def end(self, span, **args) -> Span:
        return span

    def record(self, name, t0, t1, parent=None, **args) -> Span:
        return NULL_SPAN

    def adopt(self, parent, child) -> None:
        pass

    def pin(self, t0, t1, **label) -> int:
        return 0

    def claim_loop_thread(self) -> None:
        pass

    def release_loop_thread(self) -> None:
        pass

    def anchor(self) -> None:
        pass

    def spans(self) -> list:
        return []
