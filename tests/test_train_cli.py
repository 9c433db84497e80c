"""End-to-end trainer tests: the config→train→checkpoint→resume surface
(reference train.py:57-281), run in-process on the 8-virtual-device mesh.

The key property: a run interrupted at step k and resumed equals the
uninterrupted run — stronger than the reference (which replays data from the
top after resume, train.py:214-215): with ``skip_steps`` the resumed run sees
the same batches the uninterrupted one would.
"""

import pytest

import numpy as np

from picotron_tpu.data import MicroBatchDataLoader
from picotron_tpu.train import train

from conftest import make_config


@pytest.mark.slow
def test_train_loop_and_interrupted_resume(tiny_model_kwargs, tmp_path):
    common = dict(dp=2, tp=2, mbs=2, seq=32,
                  total_train_steps=6)

    # uninterrupted 6-step run
    cfg_full = make_config(tiny_model_kwargs, **common)
    cfg_full.checkpoint.save_dir = str(tmp_path / "full")
    cfg_full.checkpoint.save_frequency = 6
    steps, tokens, loss_full = train(cfg_full)
    assert steps == 6
    assert tokens == 6 * cfg_full.tokens_per_step

    # same run stopped at 3...
    cfg_a = make_config(tiny_model_kwargs, **common)
    cfg_a.training.total_train_steps = 3
    cfg_a.checkpoint.save_dir = str(tmp_path / "ab")
    cfg_a.checkpoint.save_frequency = 3
    train(cfg_a)

    # ...then resumed to 6: identical final loss
    cfg_b = make_config(tiny_model_kwargs, **common)
    cfg_b.checkpoint.save_dir = str(tmp_path / "ab")
    cfg_b.checkpoint.save_frequency = 3
    cfg_b.checkpoint.load_path = str(tmp_path / "ab")
    steps_b, tokens_b, loss_b = train(cfg_b)
    assert steps_b == 6
    assert tokens_b == 6 * cfg_b.tokens_per_step
    assert float(loss_b) == float(loss_full)


def test_max_tokens_stop(tiny_model_kwargs, tmp_path):
    """max_tokens halts mid-schedule (reference stop condition, train.py:219)."""
    cfg = make_config(tiny_model_kwargs, dp=2, tp=2, mbs=2, seq=32,
                      total_train_steps=50)
    cfg.training.max_tokens = 3 * cfg.tokens_per_step
    steps, tokens, _ = train(cfg)
    assert steps == 3
    assert tokens == 3 * cfg.tokens_per_step


def test_loader_skip_steps_matches_replay(tiny_model_kwargs):
    cfg = make_config(tiny_model_kwargs, dp=2, mbs=2, acc=2, seq=32)
    a = MicroBatchDataLoader(cfg)
    b = MicroBatchDataLoader(cfg)
    for _ in range(5):
        next(a)
    b.skip_steps(5)
    xa, xb = next(a), next(b)
    np.testing.assert_array_equal(xa["input_ids"], xb["input_ids"])
    np.testing.assert_array_equal(xa["target_ids"], xb["target_ids"])


def test_wandb_logging_path(tiny_model_kwargs, monkeypatch):
    """use_wandb drives the full wandb call surface (init with the
    reference's run-name convention, per-step log, finish) via a stub
    module — no network, no wandb dependency."""
    import sys
    import types

    events = []
    stub = types.ModuleType("wandb")
    stub.init = lambda **kw: events.append(("init", kw)) or stub
    stub.log = lambda data, step=None: events.append(("log", step, data))
    stub.finish = lambda: events.append(("finish",))
    monkeypatch.setitem(sys.modules, "wandb", stub)

    from picotron_tpu.train import train

    cfg = make_config(tiny_model_kwargs, seq=32, mbs=2)
    cfg.training.total_train_steps = 2
    cfg.logging.use_wandb = True
    cfg.logging.run_name = "stubrun"
    steps, tokens, loss = train(cfg)
    assert steps == 2
    init_kw = events[0][1]
    assert init_kw["name"].startswith("stubrun_")
    assert "_dp1_tp1_pp1_cp1" in init_kw["name"]
    logs = [e for e in events if e[0] == "log"]
    assert len(logs) == 2 and logs[0][1] == 1 and "loss" in logs[0][2]
    assert events[-1] == ("finish",)


# --------------------------------------------------------------------------- #
# chip_smoke.py rehearsals: the whole smoke at toy size on the CPU. They
# compile a model per phase, so they live here, last in the suite.
# --------------------------------------------------------------------------- #


def _rehearse(*flags):
    import json
    import os
    import subprocess
    import sys

    root = __file__.rsplit("/tests/", 1)[0]
    # the smoke's parent must stay off jax, so it runs as a process of its
    # own, without this suite's device-count and platform settings
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    r = subprocess.run(
        [sys.executable, os.path.join(root, "chip_smoke.py"), "--rehearse",
         *flags], capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    assert r.stdout.startswith("REHEARSAL")
    return r.stdout, json.loads(r.stdout.strip().splitlines()[-1])


def test_chip_smoke_rehearsal_one_chip_phases():
    out, last = _rehearse()
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 1}}
    for phase in ("kernels", "train", "serve"):
        assert f"=== phase {phase} passed" in out
    assert "[serve:flash]" in out and "[serve:dense]" in out


def test_chip_smoke_rehearsal_four_chip_phase():
    out, last = _rehearse("--four-chip")
    assert last["ok"] is True and last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == 4
    assert "=== phase mesh passed" in out and "phase kernels" not in out
    assert "pp2_cp2 vs one_device" in out and "dp2_tp2 vs one_device" in out
