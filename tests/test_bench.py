"""Unit tests for the bench harness logic (bench.py is a driver artifact:
its size-descent and error classification decide what number gets published,
so they get the same test treatment as the framework)."""

import sys

import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])

from bench import classify_bench_error, run_descending


def test_classify_bench_error():
    assert classify_bench_error("resource_exhausted: out of hbm") == "oom"
    assert classify_bench_error("ran out of memory while allocating") == "oom"
    assert classify_bench_error(
        "exceeds the amount of memory available (need 20g)") == "oom"
    assert classify_bench_error("typeerror: bad argument") == "raise"
    assert classify_bench_error("internal: http 500") == "raise"


def _patched(monkeypatch, behavior):
    """Patch bench.run with a scripted behavior: size -> list of outcomes
    (numbers return, strings raise RuntimeError(str)); each attempt pops."""
    import bench

    calls = []

    def fake_run(cfg, **kw):
        size = cfg
        calls.append(size)
        outcome = behavior[size].pop(0)
        if isinstance(outcome, str):
            raise RuntimeError(outcome)
        return outcome

    monkeypatch.setattr(bench, "run", fake_run)
    return calls


def test_descends_on_oom(monkeypatch):
    calls = _patched(monkeypatch, {
        "big": ["resource_exhausted"], "small": [123.0]})
    cfg, tok_s = run_descending(("big", "small"), lambda s: s, tag="t")
    assert (cfg, tok_s) == ("small", 123.0)
    assert calls == ["big", "small"]


def test_unknown_error_raises(monkeypatch):
    _patched(monkeypatch, {"big": ["some assertion failed"]})
    with pytest.raises(RuntimeError, match="assertion"):
        run_descending(("big", "small"), lambda s: s, tag="t")


def test_all_sizes_fail_exits(monkeypatch):
    _patched(monkeypatch, {"big": ["out of memory"], "small": ["out of memory"]})
    with pytest.raises(SystemExit, match="failed at all sizes"):
        run_descending(("big", "small"), lambda s: s, tag="t")


def test_entry_watchdog_interrupts_wedged_entry(monkeypatch):
    """An entry that wedges in an interruptible sleep: the watchdog must
    fire instead of letting the wedge consume the whole run; a transient
    wedge (one trip) retries the same size and succeeds."""
    import time as _time

    import bench

    monkeypatch.setenv("PICOTRON_BENCH_ENTRY_TIMEOUT", "1")
    calls = []

    def fake_run(cfg, **kw):
        calls.append(cfg)
        if len(calls) == 1:
            _time.sleep(30)  # wedge: only the alarm can end this
        return 42.0

    monkeypatch.setattr(bench, "run", fake_run)
    t0 = _time.monotonic()
    cfg, tok_s = run_descending(("big", "small"), lambda s: s, tag="t")
    assert (cfg, tok_s) == ("big", 42.0)
    assert calls == ["big", "big"]  # one trip, retry same size, success
    assert _time.monotonic() - t0 < 10


def test_second_watchdog_trip_bails_with_infra_code(monkeypatch):
    """A persistently wedged entry must not pay the cap on every size:
    the second trip exits EX_INFRA, distinct from a failure of the bench
    code itself."""
    import time as _time

    import bench

    monkeypatch.setenv("PICOTRON_BENCH_ENTRY_TIMEOUT", "1")
    monkeypatch.setattr(bench, "run",
                        lambda cfg, **kw: _time.sleep(30) or 0.0)
    with pytest.raises(SystemExit) as ei:
        run_descending(("big", "small"), lambda s: s, tag="t")
    assert ei.value.code == bench.EX_INFRA


def test_entry_watchdog_disabled_and_cleared(monkeypatch):
    """0 disables the watchdog; after a successful entry no alarm is left
    pending to fire mid-publish."""
    import signal

    import bench

    monkeypatch.setenv("PICOTRON_BENCH_ENTRY_TIMEOUT", "0")
    monkeypatch.setattr(bench, "run", lambda cfg, **kw: 5.0)
    assert run_descending(("a",), lambda s: s, tag="t") == ("a", 5.0)

    monkeypatch.setenv("PICOTRON_BENCH_ENTRY_TIMEOUT", "60")
    assert run_descending(("a",), lambda s: s, tag="t") == ("a", 5.0)
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)


def _tiny_cfg():
    from picotron_tpu.config import Config

    return Config.from_dict({
        "distributed": {"use_cpu": True},
        "model": dict(num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=4, hidden_size=64,
                      intermediate_size=128, vocab_size=256,
                      max_position_embeddings=64, dtype="float32"),
        "training": {"seq_length": 32, "micro_batch_size": 1},
        "dataset": {"name": "synthetic"},
    })


def _lane_cfg():
    base = _tiny_cfg()
    base.model.hidden_size = 512  # 4 heads -> head_dim 128: 'merged' lowers
    return base


def test_flash_layout_ab_adopts_faster(monkeypatch):
    import bench

    monkeypatch.setattr(
        bench, "run",
        lambda c, **kw: 200.0 if c.model.flash_layout == "merged" else 100.0)
    cfg, tok_s = bench.try_flash_layout_ab(_lane_cfg(), 100.0)
    assert tok_s == 200.0 and cfg.model.flash_layout == "merged"


def test_flash_layout_ab_failure_fails_the_bench(monkeypatch):
    """A leg that fails is a failure of the run, not a reason to publish
    the other leg's number."""
    import bench

    def boom(c, **kw):
        raise RuntimeError("Mosaic failed to legalize")

    monkeypatch.setattr(bench, "run", boom)
    with pytest.raises(RuntimeError, match="legalize"):
        bench.try_flash_layout_ab(_lane_cfg(), 100.0)


def test_flash_layout_ab_slower_keeps_folded(monkeypatch):
    import bench

    monkeypatch.setattr(bench, "run", lambda c, **kw: 80.0)
    base = _lane_cfg()
    cfg, tok_s = bench.try_flash_layout_ab(base, 100.0)
    assert tok_s == 100.0 and cfg is base


def test_flash_layout_ab_skips_geometries_without_a_second_layout(
        monkeypatch):
    """head_dim 16 has no transpose-free layout the chip's compiler
    accepts ('bshd' is refused there): no leg is run at all."""
    import bench

    def never(c, **kw):
        raise AssertionError("no A/B leg may run")

    monkeypatch.setattr(bench, "run", never)
    base = _tiny_cfg()
    cfg, tok_s = bench.try_flash_layout_ab(base, 100.0)
    assert tok_s == 100.0 and cfg is base


def test_flash_layout_ab_picks_merged_for_lane_aligned_heads(monkeypatch):
    """head_dim % 128 == 0 (the 7B geometry) must A/B the hardware-lowerable
    'merged' layout, not the Mosaic-rejected 'bshd'."""
    import bench

    tried = []

    def fake_run(c, **kw):
        tried.append(c.model.flash_layout)
        return 200.0

    monkeypatch.setattr(bench, "run", fake_run)
    base = _tiny_cfg()
    base.model.hidden_size = 512  # 4 heads -> head_dim 128
    cfg, tok_s = bench.try_flash_layout_ab(base, 100.0)
    assert tried == ["merged"]
    assert tok_s == 200.0 and cfg.model.flash_layout == "merged"
