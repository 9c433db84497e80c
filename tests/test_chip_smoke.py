"""Guards around the chip path: ``chip_smoke.py``'s contract, the compile
cache helper and the peaks table. Nothing here compiles or starts a
process: the two slow end-to-end rehearsals live in tests/test_train_cli.py.
"""

import ast
import inspect
import io
import json
import os
import sys
import types

import pytest

import jax

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])

import chip_smoke  # noqa: E402
from picotron_tpu import utils  # noqa: E402

TPU = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


# --------------------------------------------------------------------------- #
# the device gate and the last line
# --------------------------------------------------------------------------- #


def test_no_chip_no_rehearsal_is_a_failure(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke._device(rehearse=False)
    assert e.value.code not in (0, None) and "need 'tpu'" in str(e.value.code)
    assert '"ok": true' not in capsys.readouterr().out


def test_rehearsal_accepts_only_the_cpu():
    assert chip_smoke._device(rehearse=True)["platform"] == "cpu"


def test_last_line_has_exactly_the_contract_keys():
    line = json.loads(chip_smoke.final_line(TPU, rehearse=False))
    assert line == {"ok": True, "device": TPU}
    assert list(line) == ["ok", "device"]
    assert set(line["device"]) == {"platform", "kind", "count"}


def test_rehearsal_never_vouches_for_a_tpu():
    with pytest.raises(SystemExit):
        chip_smoke.final_line(TPU, rehearse=True)
    assert json.loads(chip_smoke.final_line(CPU, rehearse=True))[
        "device"]["platform"] == "cpu"


def test_real_run_never_vouches_for_a_cpu():
    with pytest.raises(SystemExit):
        chip_smoke.final_line(CPU, rehearse=False)


# --------------------------------------------------------------------------- #
# the parent: phases as children, one after another, no going on
# --------------------------------------------------------------------------- #


class _FakeChild:
    """Stands in for a phase child: scripted stdout and exit code."""

    def __init__(self, cmd, lines, rc):
        self.cmd, self.stdout, self._rc = cmd, io.StringIO("".join(lines)), rc

    def wait(self, timeout=None):
        return self._rc

    def poll(self):
        return self._rc


def _fake_children(monkeypatch, script):
    """Patch Popen with children scripted per phase name:
    {phase: (device | None, rc)}. Returns the list of launched commands."""
    launched = []

    def popen(cmd, **kw):
        assert kw["stdout"] is not None and kw["cwd"] == chip_smoke.HERE
        phase = cmd[cmd.index("--phase") + 1]
        launched.append(cmd)
        device, rc = script[phase]
        lines = [f"[{phase}] working\n"]
        if device is not None:
            lines.append(chip_smoke.RESULT_TAG
                         + json.dumps({"ok": True, "device": device}) + "\n")
        return _FakeChild(cmd, lines, rc)

    monkeypatch.setattr(chip_smoke.subprocess, "Popen", popen)
    monkeypatch.setattr(chip_smoke, "_parent_is_clean", lambda: True)
    return launched


def test_all_phases_pass_prints_the_contract_line_last(monkeypatch, capsys):
    launched = _fake_children(monkeypatch, {
        p: (TPU, 0) for p in chip_smoke.ONE_CHIP_PHASES})
    assert chip_smoke.main([]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == {"ok": True, "device": TPU}
    assert [c[c.index("--phase") + 1] for c in launched] == \
        ["kernels", "train", "serve"]
    assert not any(chip_smoke.RESULT_TAG in line for line in out)


def test_failed_phase_stops_the_run_without_ok(monkeypatch, capsys):
    launched = _fake_children(monkeypatch, {
        "kernels": (TPU, 0), "train": (None, 1), "serve": (TPU, 0)})
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert len(launched) == 2  # serve never started
    assert '"ok": true' not in capsys.readouterr().out


def test_phase_without_a_result_is_a_failure(monkeypatch, capsys):
    _fake_children(monkeypatch, {
        "kernels": (None, 0), "train": (TPU, 0), "serve": (TPU, 0)})
    with pytest.raises(SystemExit):
        chip_smoke.main([])
    assert '"ok": true' not in capsys.readouterr().out


def test_four_chip_option_runs_only_the_mesh_phase(monkeypatch, capsys):
    four = dict(TPU, count=4)
    launched = _fake_children(monkeypatch, {"mesh": (four, 0)})
    assert chip_smoke.main(["--four-chip"]) == 0
    assert [c[c.index("--phase") + 1] for c in launched] == ["mesh"]
    assert json.loads(capsys.readouterr().out.strip().splitlines()[-1]) == \
        {"ok": True, "device": four}


@pytest.mark.parametrize("argv,count", [([], 4), (["--four-chip"], 1)])
def test_wrong_device_count_is_a_failure(monkeypatch, capsys, argv, count):
    dev = dict(TPU, count=count)
    _fake_children(monkeypatch, {p: (dev, 0) for p in chip_smoke.PHASES})
    with pytest.raises(SystemExit):
        chip_smoke.main(argv)
    assert '"ok": true' not in capsys.readouterr().out


def test_parent_that_touched_jax_refuses_to_start_children(monkeypatch):
    monkeypatch.setattr(chip_smoke.subprocess, "Popen",
                        lambda *a, **k: pytest.fail("child started"))
    assert "jax" in sys.modules  # this test process has it
    with pytest.raises(SystemExit):
        chip_smoke.run_phases(("kernels",), rehearse=False)


def test_parent_module_imports_no_jax():
    """Top-level imports of chip_smoke.py are stdlib only: the parent can
    never hold the chip."""
    tree = ast.parse(inspect.getsource(chip_smoke))
    top = [n for n in tree.body if isinstance(n, (ast.Import, ast.ImportFrom))]
    names = {a.name.split(".")[0] for n in top if isinstance(n, ast.Import)
             for a in n.names} | {n.module.split(".")[0] for n in top
                                  if isinstance(n, ast.ImportFrom)}
    assert names <= {"__future__", "argparse", "json", "os", "re",
                     "subprocess", "sys", "time"}


def test_phase_that_raises_gives_nonzero_and_no_result(monkeypatch, capsys):
    def boom(rehearse):
        raise RuntimeError("kernel disagrees")

    monkeypatch.setitem(chip_smoke.PHASES, "kernels", boom)
    with pytest.raises(RuntimeError):
        chip_smoke.main(["--phase", "kernels"])
    assert chip_smoke.RESULT_TAG not in capsys.readouterr().out


def test_rehearsal_child_insists_on_the_cpu_pin(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setitem(chip_smoke.PHASES, "kernels",
                        lambda rehearse: pytest.fail("phase ran"))
    with pytest.raises(SystemExit):
        chip_smoke.main(["--phase", "kernels", "--rehearse"])


def test_smoke_writes_its_own_full_width_configs():
    cfg = chip_smoke.train_config(False, mbs=chip_smoke.TRAIN_MBS)
    m = cfg["model"]
    assert (m["num_hidden_layers"], m["hidden_size"], m["vocab_size"],
            m["dtype"]) == (24, 2048, 49152, "bfloat16")
    assert cfg["training"]["seq_length"] == 2048
    assert not cfg["distributed"]["use_cpu"]
    inf = chip_smoke.serve_config(False, "flash")["inference"]
    assert inf == {"attend_impl": "flash", "attend_fallback": False}


def test_kernels_held_demands_the_compiled_kernel(monkeypatch, tmp_path,
                                                  capsys):
    """The proof that the compiled kernel ran: a lowered program of the
    phase must hold the custom call by name."""
    monkeypatch.setattr(chip_smoke, "WORK", str(tmp_path))
    ir = tmp_path / "ir" / "serve"
    ir.mkdir(parents=True)
    (ir / "jax_ir0001_jit__decode_impl_compile.mlir").write_text(
        'stablehlo.custom_call @tpu_custom_call(%0) {kernel_name = '
        '"flash_decode_attention"}\n')
    (ir / "jax_ir0002_jit_other_compile.mlir").write_text("stablehlo.add\n")
    chip_smoke._kernels_held("serve", False,
                             {"_decode": ["flash_decode_attention"]})
    assert "jit__decode_impl holds flash_decode_attention x1" in \
        capsys.readouterr().out
    with pytest.raises(SystemExit):
        chip_smoke._kernels_held("serve", False, {"": ["quant_matmul"]})


# --------------------------------------------------------------------------- #
# the compile cache helper
# --------------------------------------------------------------------------- #


@pytest.fixture
def cache_config():
    """Record what enable_compile_cache sets, and put it back."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_cache_dir_from_the_environment_is_left_alone(monkeypatch,
                                                      cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where/else")
    was = jax.config.jax_compilation_cache_dir
    assert utils.enable_compile_cache() == "/some/where/else"
    assert jax.config.jax_compilation_cache_dir == was  # nothing set in code


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(monkeypatch,
                                                             cache_config):
    import tempfile
    import time

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = utils.enable_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(utils.__file__)))
    assert first == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert not first.startswith(tempfile.gettempdir() + os.sep)
    # another process, another moment: the same path
    monkeypatch.setattr(os, "getpid", lambda: 424242)
    monkeypatch.setattr(time, "time", lambda: 1.0)
    assert utils.enable_compile_cache() == first
    assert "424242" not in first


# --------------------------------------------------------------------------- #
# peaks: unknown accelerator = error, cpu = no MFU
# --------------------------------------------------------------------------- #


def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197e12),
                                       ("TPU v4", 275e12)])
def test_peak_flops_known_chip(kind, peak):
    assert utils.peak_flops_per_chip(_dev("tpu", kind)) == peak


def test_peak_flops_cpu_is_none():
    assert utils.peak_flops_per_chip(_dev("cpu", "cpu")) is None
    assert utils.get_mfu(1.0, 1, 1, 1, 1, None) is None


@pytest.mark.parametrize("platform,kind", [("tpu", "TPU v9 mega"),
                                           ("gpu", "NVIDIA H100")])
def test_peak_flops_unknown_accelerator_raises(platform, kind):
    with pytest.raises(ValueError, match="no peak"):
        utils.peak_flops_per_chip(_dev(platform, kind))


# --------------------------------------------------------------------------- #
# code for the one installation
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("fn", [utils.shard_map, utils.typeof_vma])
def test_jax_shims_have_one_branch(fn):
    tree = ast.parse(inspect.getsource(fn))
    branches = [n for n in ast.walk(tree)
                if isinstance(n, (ast.If, ast.Try, ast.IfExp))]
    assert not branches
    assert "experimental" not in inspect.getsource(fn)
