"""The parts of a round (ISSUE 37; docs/OBSERVABILITY.md "The parts of a
round"): ``issue/operands`` and ``issue/enqueue`` inside ``step/issue``,
``sync/wait`` and ``sync/fetch`` inside ``step/sync``.

- one observation of ``picotron_round_part_seconds{part}`` a dispatch, each
  part inside its phase, the phases still tiling the round (manual clock,
  serial and pipelined), a re-dispatch observing its parts again;
- under an open capture the four are ``pt:`` annotations nested in their
  phase's; ``obs.enabled: false`` writes neither family nor span;
- a capture traces no Python frames;
- the twelve readers of ``benchmarks/layer_metrics/`` on synthetic scrapes.
"""

import json
import os
import threading

import pytest

import jax

from picotron_tpu import obs as obs_mod
from picotron_tpu.inference import ContinuousBatcher, Request
from picotron_tpu.obs import MetricsRegistry, Obs, SpanTracer, tracing
from picotron_tpu.obs.metrics import parse_prometheus
from test_obs import _ManualClock, _engine, _phase_reads

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = {"issue/operands": "step/issue", "issue/enqueue": "step/issue",
         "sync/wait": "step/sync", "sync/fetch": "step/sync"}


@pytest.fixture(scope="module")
def engines():
    """One serial and one pipelined engine for the whole file: a test swaps
    ``engine.obs`` and builds its own batcher (the compiled programs stay)."""
    made = {}

    def get(overlap):
        if overlap not in made:
            made[overlap] = _engine(slots=2, overlap=overlap,
                                    decode_block_len=2)[1:]
        return made[overlap]

    return get


def _part_reads(registry):
    return _phase_reads(registry, "picotron_round_part_seconds")


def _clocked(engine, params, monkeypatch, costs):
    """A batcher on a manual clock whose named methods cost what ``costs``
    says: {(object name, method): seconds}."""
    clock = _ManualClock()
    engine.obs = Obs(enabled=True, registry=MetricsRegistry(),
                     tracer=SpanTracer(ring=4096, clock=clock))
    b = ContinuousBatcher(engine, params, clock=clock)
    b._retry = dict(b._retry, backoff=0.0)
    for (who, name), seconds in costs.items():
        obj = {"engine": engine, "batcher": b, "jax": jax}[who]
        inner = getattr(obj, name)

        def wrapped(*a, _inner=inner, _s=seconds, **kw):
            clock.t += _s
            return _inner(*a, **kw)

        monkeypatch.setattr(obj, name, wrapped)
    return b, clock


COSTS = {("engine", "_hook"): 0.25e-3,            # step/issue's own time
         ("engine", "_round_operands"): 1e-3,     # issue/operands
         ("engine", "_dispatch"): 2e-3,           # issue/enqueue (prefill's
         #                                          enqueues: step/admit)
         ("jax", "block_until_ready"): 5e-3,      # sync/wait: the one
         #                              wait of the inference package
         ("batcher", "_note_sync_end"): 0.5e-3}   # step/deliver


@pytest.mark.parametrize("overlap", [False, True])
def test_parts_lie_inside_their_phases_once_a_dispatch(overlap, engines,
                                                       monkeypatch):
    engine, params = engines(overlap)
    b, clock = _clocked(engine, params, monkeypatch, COSTS)
    for i in range(3):
        b.submit(Request(f"p{i}", [3 + i, 5, 7], max_new_tokens=9))
    rounds, wall = 0, 0.0
    while b.busy:
        t0 = clock()
        b.step()
        wall += clock() - t0
        rounds += 1
    n = b.decode_dispatches
    assert rounds >= 4 and n in (rounds, rounds - 1)
    parts, ph = _part_reads(engine.obs.registry), \
        _phase_reads(engine.obs.registry)
    assert set(parts) == set(PARTS)
    assert all(parts[p]["count"] == n for p in PARTS)  # once a dispatch
    assert parts["issue/operands"]["sum"] == pytest.approx(n * 1e-3)
    assert parts["issue/enqueue"]["sum"] == pytest.approx(n * 2e-3)
    assert parts["sync/wait"]["sum"] == pytest.approx(n * 5e-3)
    assert parts["sync/fetch"]["sum"] == pytest.approx(0.0, abs=1e-12)
    # the sync is its two parts; the issue keeps its own time beside them
    assert parts["sync/wait"]["sum"] + parts["sync/fetch"]["sum"] \
        == pytest.approx(ph["step/sync"]["sum"])
    assert ph["step/issue"]["sum"] == pytest.approx(n * 3.25e-3)
    assert parts["issue/operands"]["sum"] + parts["issue/enqueue"]["sum"] \
        < ph["step/issue"]["sum"]
    # the phases are what they were: the same labels, tiling the wall time
    assert set(ph) == {"step/plan", "step/admit", "step/issue",
                       "step/sync", "step/deliver"}
    assert sum(v["sum"] for v in ph.values()) == pytest.approx(wall)
    # on the ring every part lies inside a span of its phase, same thread
    spans = engine.obs.tracer.spans()
    for part, phase in PARTS.items():
        inner = [s for s in spans if s.name == part]
        assert len(inner) == n
        for s in inner:
            assert any(o.name == phase and o.tid == s.tid
                       and o.t0 <= s.t0 and s.t1 <= o.t1 for o in spans), part


def test_a_redispatch_observes_its_parts_again_and_the_phase_once(
        engines, monkeypatch):
    engine, params = engines(False)
    b, clock = _clocked(engine, params, monkeypatch, COSTS)

    class FailsTheGroupOnce:
        """The first round's dispatch of both slots together fails, attempt
        and retries; each slot alone goes through."""

        def __init__(self):
            self.left = b._retry["attempts"]

        def before_dispatch(self, kind, slots):
            if kind == "decode" and len(slots) > 1 and self.left:
                self.left -= 1
                raise RuntimeError("group dispatch fails")

        def poison_logits(self, kind):
            return False

    monkeypatch.setattr(engine, "hooks", FailsTheGroupOnce())
    for i in range(2):
        b.submit(Request(f"p{i}", [3 + i, 5, 7], max_new_tokens=9))
    b.step()  # admits both, fails them together, runs each alone
    assert b.decode_dispatches == 2 and engine.hooks.left == 0
    parts, ph = _part_reads(engine.obs.registry), \
        _phase_reads(engine.obs.registry)
    assert all(parts[p]["count"] == 2 for p in PARTS)
    # each dispatch packed and fetched for itself (ISSUE 38): the copies
    # follow the dispatches, not the round, and a failed attempt made none
    prom = parse_prometheus(engine.obs.registry.prometheus())
    assert {k.split('"')[1]: v for k, v in prom.items()
            if k.startswith("picotron_round_copies_total")} == {
        "h2d": 2, "d2h": 2}
    assert ph["step/issue"]["count"] == ph["step/sync"]["count"] == 1
    assert parts["sync/wait"]["sum"] == pytest.approx(2 * 5e-3)
    assert ph["step/sync"]["sum"] == pytest.approx(2 * 5e-3)
    # the failed attempts never reached a part: the phase holds their hooks
    assert ph["step/issue"]["sum"] == pytest.approx(
        2 * 3.25e-3 + b._retry["attempts"] * 0.25e-3)
    while b.busy:
        b.step()
    assert {r.finish_reason for r in b.take_results().values()} == {"length"}


class _Annotations:
    """``jax.profiler.TraceAnnotation`` stubbed: what was entered, on which
    thread, under which open annotations."""

    def __init__(self):
        self.entered = []  # (name, thread, the names open around it)
        self._open = []

    def __call__(self, name, **kw):
        log = self

        class One:
            def __enter__(self):
                log.entered.append((name, threading.get_ident(),
                                    tuple(log._open)))
                log._open.append(name)
                return self

            def __exit__(self, *exc):
                assert log._open.pop() == name  # scopes close in order
                return False

        return One()


def test_the_parts_are_annotated_inside_their_phases_annotation(
        engines, monkeypatch):
    engine, params = engines(False)
    seen = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    engine.obs = Obs(enabled=True, registry=MetricsRegistry(),
                     tracer=SpanTracer(ring=256))
    b = ContinuousBatcher(engine, params)
    b.submit(Request("p", [3, 5, 7], max_new_tokens=5))
    engine.obs.tracer.claim_loop_thread()
    tracing.set_capture_open(True)
    try:
        b.step()
        b.step()
    finally:
        tracing.set_capture_open(False)
        engine.obs.tracer.release_loop_thread()
    me = threading.get_ident()
    for part, phase in PARTS.items():
        hits = [e for e in seen.entered if e[0] == "pt:" + part]
        assert len(hits) == 2, part  # one a dispatch, two rounds
        assert all(tid == me and around == ("pt:" + phase,)
                   for _, tid, around in hits), (part, hits)
    assert {e[0] for e in seen.entered} == {
        "pt:" + n for n in (*PARTS, "step/plan", "step/admit", "step/issue",
                            "step/sync", "step/deliver")}


def test_obs_disabled_no_part_family_and_no_span(engines, monkeypatch):
    engine, params = engines(False)
    seen = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", seen)
    engine.obs = obs_mod.null_obs()
    b = ContinuousBatcher(engine, params)
    tracing.set_capture_open(True)
    try:
        res = b.run([Request("q", [3, 4, 5], max_new_tokens=4)])
        with engine.obs.part("sync/wait") as span:
            pass
    finally:
        tracing.set_capture_open(False)
    assert res["q"].finish_reason == "length" and b.decode_dispatches >= 1
    assert span.t1 is None and seen.entered == []
    assert engine.obs.registry.prometheus() == ""
    assert engine.obs.tracer.spans() == []


def test_a_capture_traces_no_python_frames(tmp_path, monkeypatch):
    from picotron_tpu.obs import ProfileCapture

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append((d, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    cap = ProfileCapture(str(tmp_path), tracer=SpanTracer(ring=8))
    assert cap.start()["ok"] and cap.stop()["ok"]
    (d, kw), = calls
    assert d == str(tmp_path) and set(kw) == {"profiler_options"}
    options = kw["profiler_options"]
    assert options.python_tracer_level == 0
    # the host tracer writes the TraceAnnotations: left where jax has it
    assert options.host_tracer_level \
        == jax.profiler.ProfileOptions().host_tracer_level > 0


# ---- the readers ------------------------------------------------------------

NEW = [f"engine.{stem}_ms{suffix}"
       for stem in ("issue_operands", "issue_enqueue", "sync_wait",
                    "sync_fetch")
       for suffix in ("", ".chat", ".tput")]


def _scrape(rounds, dispatches, seconds):
    rows = [f'picotron_round_phase_seconds_count{{phase="step/issue"}} '
            f'{rounds}',
            f'picotron_round_phase_seconds_sum{{phase="step/issue"}} 9.9']
    for part in PARTS:
        rows += [f'picotron_round_part_seconds_bucket{{part="{part}",'
                 f'le="+Inf"}} {dispatches}',
                 f'picotron_round_part_seconds_count{{part="{part}"}} '
                 f'{dispatches}',
                 f'picotron_round_part_seconds_sum{{part="{part}"}} '
                 f'{seconds[part]}']
    return "\n".join(rows) + "\n"


@pytest.mark.parametrize("name", NEW)
def test_a_part_reader_gives_the_mean_a_round_or_nothing(name):
    from benchmarks.run import load_reader

    read = load_reader("layer_metrics", name)
    part = name.split(".")[1][:-len("_ms")].replace("_", "/")
    assert part in PARTS
    before = _scrape(10, 10, dict.fromkeys(PARTS, 1.0))
    # 100 rounds, one of which dispatched three times; every part its own sum
    after = _scrape(110, 112, {"issue/operands": 1.05, "issue/enqueue": 1.3,
                               "sync/wait": 6.0, "sync/fetch": 1.02})
    want = {"issue/operands": 0.5, "issue/enqueue": 3.0,
            "sync/wait": 50.0, "sync/fetch": 0.2}[part]
    assert read({"metrics_before": before, "metrics_after": after}) \
        == pytest.approx(want)
    # the parent: rounds, and no such family; no scrapes; no round
    phases_only = "\n".join(r for r in after.splitlines() if "part" not in r)
    assert read({"metrics_before": phases_only,
                 "metrics_after": phases_only.replace("110", "210")}) is None
    assert read({}) is None
    assert read({"metrics_before": after, "metrics_after": after}) is None


def test_the_part_readers_are_listed_for_the_serving_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"] for w in manifest["workloads"]}
    moved = {m["name"]: set(m.get("workloads", cells))
             for m in manifest["end_to_end"]}
    # appended in this order by PR 37; later PRs append behind them
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW[0])
    entries = manifest["per_layer"][at:at + len(NEW)]
    assert [m["name"] for m in entries] == NEW
    served = set()
    for m in entries:
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
        assert (m["unit"], m["better"], m["source"]) == \
            ("ms", "lower", "program_counter")
        assert m["layer"] == ("batcher" if "sync_fetch" in m["name"]
                              else "engine programs")
        # only cells of the manifest, each reporting the metric it moves
        assert set(m["workloads"]) <= cells & moved[m["moves"]]
        served |= set(m["workloads"])
    assert served == {c for c in cells if ".serve-" in c}
