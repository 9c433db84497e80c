"""trace_reduce on a small ProfileData-shaped fixture with nested and
overlapping events: union, not sum."""

from types import SimpleNamespace as NS

import pytest

from benchmarks import trace_reduce as tr


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1000, duration_ns=dur_us * 1000)


def fixture():
    ops = NS(name="XLA Ops", events=[
        ev("while.1", 0, 100),       # holds the next three
        ev("fusion.2", 10, 30),
        ev("flash_fwd", 50, 40),
        ev("fusion.2", 92, 5),
        ev("copy.3", 200, 50),       # after an idle gap of 100 us
        ev("copy.3", 240, 30),       # overlaps the one before by 10 us
    ])
    modules = NS(name="XLA Modules", events=[
        ev("jit_step", 0, 100), ev("jit_step", 200, 70)])
    host = NS(name="python", events=[
        ev("bench:step", 0, 150), ev("bench:load_batch", 150, 30),
        ev("bench:sync", 180, 100), ev("other", 0, 500)])
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[ops, modules]),
        NS(name="/host:CPU", lines=[host]),
    ])


def test_union_not_sum():
    ivs = tr.intervals(fixture().planes[0].lines[0])
    assert sum(e - s for s, e, _ in ivs) == pytest.approx(255e-6)
    assert tr.union_seconds(ivs) == pytest.approx(170e-6)  # 100 + 70


def test_self_time_subtracts_children():
    own = tr.self_seconds(tr.intervals(fixture().planes[0].lines[0]))
    assert own["while.1"] == [1, pytest.approx(25e-6)]  # 100 - 30 - 40 - 5
    assert own["fusion.2"] == [2, pytest.approx(35e-6)]
    assert own["flash_fwd"] == [1, pytest.approx(40e-6)]
    assert tr.by_base_name(own, "fusion") == (2, pytest.approx(35e-6))


def test_names_are_shortened():
    assert tr.short_name("%flash_fwd.17 = (bf16[96,2048,64]{2,1,0}) "
                         "custom-call(bf16[96] %bitcast.561)") == "flash_fwd.17"
    assert tr.short_name("jit__step(3253523314721878371)") == "jit__step"
    assert tr.short_name("bench:sync") == "bench:sync"
    assert tr.base_name("flash_fwd.17") == "flash_fwd"
    assert tr.base_name("copy") == "copy"


def test_reduce_busy_idle_and_named_gaps():
    out = tr.reduce(fixture(), window_s=400e-6, n_devices=1)
    assert out["busy_s"] == pytest.approx(170e-6)
    assert out["window_s"] == pytest.approx(400e-6)
    assert out["modules"]["jit_step"] == [2, pytest.approx(170e-6)]
    # the one gap, 100..200 us, is split among the spans that cover it
    gaps = dict(out["idle_gaps"])
    assert gaps["bench:step"] == pytest.approx(50e-6)
    assert gaps["bench:load_batch"] == pytest.approx(30e-6)
    assert gaps["bench:sync"] == pytest.approx(20e-6)
    assert out["device_ops"][0][0] == "copy.3"


def test_no_device_ops_gives_nothing():
    pd = NS(planes=[NS(name="/host:CPU", lines=[])])
    assert tr.reduce(pd, 1.0, 1) is None


def test_gaps_are_named_by_the_loop_threads_spans_only():
    """The program's scoped spans under a capture: ``pt:`` on the dispatch
    loop's thread, ``pt.req:`` on the handler threads. A gap is named by
    the innermost ``pt:`` span; a handler's span that covers it (a client
    waiting in ``submit()`` for the whole gap) and the anchors name
    nothing."""
    ops = NS(name="XLA Ops", events=[ev("fusion.1", 0, 100),
                                     ev("fusion.1", 200, 100)])
    loop = NS(name="python3", events=[
        ev("pt.anchor", 0, 1),
        ev("pt:step/sync", 0, 110), ev("pt:step/deliver", 110, 30),
        ev("pt:loop/results", 140, 20), ev("pt:loop/lock_wait", 160, 10),
        ev("pt:step/plan", 170, 25),
        ev("pt:step/admit", 175, 10),  # nested in plan: the innermost wins
        ev("pt:step/issue", 195, 10)])
    handler = NS(name="python3", events=[
        ev("pt.req:submit/lock_wait", 90, 120)])
    pd = NS(planes=[NS(name="/device:TPU:0", lines=[ops]),
                    NS(name="/host:CPU", lines=[loop, handler])])
    gaps = dict(tr.reduce(pd, window_s=300e-6, n_devices=1)["idle_gaps"])
    assert gaps == {
        "pt:step/sync": pytest.approx(10e-6),
        "pt:step/deliver": pytest.approx(30e-6),
        "pt:loop/results": pytest.approx(20e-6),
        "pt:loop/lock_wait": pytest.approx(10e-6),
        "pt:step/plan": pytest.approx(15e-6),
        "pt:step/admit": pytest.approx(10e-6),
        "pt:step/issue": pytest.approx(5e-6),
    }
