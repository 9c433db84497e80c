"""Unified telemetry: metrics registry, span tracer, export surfaces.

One observability plane shared by training and serving
(docs/OBSERVABILITY.md). Three layers:

- ``obs.metrics`` — thread-safe counters/gauges/histograms behind a
  registry; Prometheus text exposition; ``CounterDict`` dict-semantics
  views for the pre-existing counter maps.
- ``obs.tracing`` — begin/end spans with parent links in a bounded ring,
  exported as Chrome-trace JSON (``tools/trace_dump.py``,
  ``GET /tracez``).
- ``obs.profiler`` — on-demand timed ``jax.profiler`` captures
  (SIGUSR2 / ``POST /profilez``).
- ``obs.stalls`` — the stall judge: every phase of a loop against its own
  recent median, the excess counted, the slow round's record and spans
  kept (``Obs.stalls``).

Ownership model:

- each ``InferenceEngine`` (and each ``train()`` run) owns a FRESH
  registry via ``Obs.from_config(cfg.obs)`` — counters start at zero per
  server/run, so ``GET /metrics`` agrees with that server's ``/statz``
  even when several engines share a process (tests);
- ``GLOBAL_REGISTRY`` holds process-wide counters owned by no run in
  particular (resilience retries, emergency saves) — export surfaces
  render it alongside the local registry;
- ``GLOBAL_TRACER`` is the one process span ring (like the logging
  root): engine, batcher, serve, train, and ``comm_trace`` all record
  into it, so a trace dump interleaves every subsystem on one timeline.
  ``obs.enabled: false`` swaps in null instruments — every record call
  no-ops and the hot paths carry zero bookkeeping.
"""

from __future__ import annotations

from typing import Optional

from picotron_tpu.obs.metrics import (  # noqa: F401 - public surface
    Counter,
    CounterDict,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullRegistry,
    percentiles_of,
)
from picotron_tpu.obs.jsonl import MetricsJsonl  # noqa: F401
from picotron_tpu.obs.profiler import ProfileCapture, install_sigusr2  # noqa: F401
from picotron_tpu.obs.stalls import (  # noqa: F401
    NullStallWatch,
    StallWatch,
    admit_key,
    install_gc_pause_counter,
)
from picotron_tpu.obs.tracing import NullTracer, Span, SpanTracer  # noqa: F401

# Process-wide surfaces (see module docstring).
GLOBAL_REGISTRY = MetricsRegistry()
GLOBAL_TRACER = SpanTracer()
_NULL_TRACER = NullTracer()


# The serving dispatch loop's phases (docs/OBSERVABILITY.md "Dispatch-loop
# phases"): one histogram family, one label value per phase.
PHASE_HISTOGRAM = "picotron_round_phase_seconds"
# What a phase holds inside ("The parts of a round", same document): a
# family beside the phases, because a part lies INSIDE its phase and a
# nested label would count twice for whoever sums over ``phase``.
PART_HISTOGRAM = "picotron_round_part_seconds"


class _Timed:
    """``Obs.timed``'s context: a scoped span and, from the span's own two
    clock reads, one observation of a histogram; ``then`` (the stall judge)
    is handed the ended span."""

    __slots__ = ("_scoped", "_hist", "_span", "_then")

    def __init__(self, scoped, hist, then=None):
        self._scoped = scoped
        self._hist = hist
        self._then = then

    def __enter__(self) -> Span:
        self._span = self._scoped.__enter__()
        return self._span

    def __exit__(self, exc_type, exc, tb) -> None:
        self._scoped.__exit__(exc_type, exc, tb)
        if self._span.t1 is not None:  # the null span never ends
            self._hist.observe(self._span.t1 - self._span.t0)
            if self._then is not None:
                self._then(self._span)


class RoundPhases:
    """Tiles one scheduler round into named phases: ``to(name)`` ends the
    open phase and opens the next one as a scoped span (so it reaches an
    open profiler capture), ``close()`` ends the round and observes each
    phase's summed seconds ONCE in ``picotron_round_phase_seconds``. A
    phase entered twice in a round (plan around admit; issue and sync of
    an isolation re-dispatch) is two spans and one observation, and the
    stall judge (``Obs.stalls``) is told each phase as that one
    observation, under its ``key``: what legitimately sets its length."""

    def __init__(self, obs: "Obs"):
        self._obs = obs
        self._scoped = None
        self._span = None
        self._t0 = None  # where the round began
        self._seconds: dict = {}
        self._keys: dict = {}

    def to(self, name: str, key: str = "") -> None:
        """Open phase ``name``. A phase opened under a key a second time in
        one round is a re-dispatch, and judged as one."""
        self._end()
        self._scoped = self._obs.tracer.span(name)
        self._span = self._scoped.__enter__()
        if self._t0 is None:
            self._t0 = self._span.t0
        if key:
            self._keys[name] = (key + "+redispatch" if name in self._keys
                                else key)

    def key(self, name: str, key: str) -> None:
        """Phase ``name`` of this round is judged under ``key`` (known
        only when the phase has done its work: what an admit dispatched)."""
        self._keys[name] = key

    def _end(self) -> None:
        if self._scoped is None:
            return
        self._scoped.__exit__(None, None, None)
        s, self._scoped = self._span, None
        if s.t1 is not None:
            self._seconds[s.name] = (self._seconds.get(s.name, 0.0)
                                     + s.t1 - s.t0)

    def close(self, seq: int = 0, facts=None) -> None:
        """End round ``seq``; ``facts()`` is what the caller knows of it,
        asked for only if the judge keeps a record of the round."""
        self._end()
        for name, seconds in self._seconds.items():
            self._obs.phase_histogram(name).observe(seconds)
        if self._seconds:
            self._obs.stalls.round_closed(seq, self._seconds, self._keys,
                                          self._t0, self._span.t1, facts)
        self._seconds.clear()
        self._keys.clear()
        self._t0 = None


class Obs:
    """The bundle a subsystem carries: its registry + the shared tracer,
    with one ``enabled`` flag gating both."""

    def __init__(self, enabled: bool = True,
                 registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[SpanTracer] = None):
        self.enabled = bool(enabled)
        if not self.enabled:
            self.registry = registry or NullRegistry()
            self.tracer = tracer or _NULL_TRACER
        else:
            self.registry = registry or MetricsRegistry()
            self.tracer = tracer or GLOBAL_TRACER
        # the stall judge, beside the registry it counts into and the ring
        # whose spans it pins (obs/stalls.py)
        self.stalls = (StallWatch(self.registry, self.tracer)
                       if self.enabled else NullStallWatch())
        self._phase_hists: dict = {}
        self._part_hists: dict = {}

    def phase_histogram(self, name: str) -> Histogram:
        h = self._phase_hists.get(name)
        if h is None:
            h = self._phase_hists[name] = self.registry.histogram(
                PHASE_HISTOGRAM,
                "dispatch-loop host time by phase, one observation a round",
                phase=name)
        return h

    def timed(self, name: str, hist: Histogram, **args) -> _Timed:
        """``with obs.timed(name, hist):`` — ``tracer.span(name)`` plus
        one observation of ``hist``, from the same two clock reads."""
        return _Timed(self.tracer.span(name, **args), hist)

    def phase(self, name: str) -> _Timed:
        """``timed`` into ``picotron_round_phase_seconds{phase=name}``, and
        judged as an interval of its own if ``stalls`` has the name
        registered (the front end's two loop phases)."""
        return _Timed(self.tracer.span(name), self.phase_histogram(name),
                      self.stalls.interval)

    def part(self, name: str) -> _Timed:
        """``timed`` into ``picotron_round_part_seconds{part=name}``: one
        observation every time the part ends (a re-dispatch runs its
        parts again), where a phase is observed once a round. The judge
        sums it into the open round's record."""
        h = self._part_hists.get(name)
        if h is None:
            h = self._part_hists[name] = self.registry.histogram(
                PART_HISTOGRAM,
                "host time of a round's parts inside step/issue and "
                "step/sync, one observation a dispatch", part=name)
        return _Timed(self.tracer.span(name), h, self.stalls.part)

    @classmethod
    def from_config(cls, ocfg) -> "Obs":
        """Build from a config ``obs`` section (config.ObsConfig)."""
        if not ocfg.enabled:
            return cls(enabled=False)
        GLOBAL_TRACER.resize(ocfg.span_ring)
        install_gc_pause_counter(GLOBAL_REGISTRY)
        return cls(enabled=True,
                   registry=MetricsRegistry(
                       sample_window=ocfg.sample_window))


def null_obs() -> Obs:
    return Obs(enabled=False)


def global_counter(name: str, help: str = "", **labels) -> Counter:
    """A counter on the process-wide registry (resilience retries,
    emergency saves, ... — owned by no single run)."""
    return GLOBAL_REGISTRY.counter(name, help, **labels)
