"""``kernels.ssm_step_roofline`` (PR 57), a new case beside the files that
exist: the reader on recorded runs of the two cells that list it and on the
programs that hold no such op. By hand (``python -m pytest
benchmarks/tests/test_ssm_step.py``)."""

import json
import os

import pytest

from benchmarks import common

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "kernels.ssm_step_roofline"
CELLS = {"nemotron-3-super-ep4-l11": "serve-reasoning-decode",
         "granite-4.0-h-small-ep2-l10": "serve-chat-closed"}


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def recorded(name, slots, ops):
    """A traced tail of three seconds with ``slots`` requests streaming all
    through it and ``ops`` on the device: {name: (calls, seconds)}."""
    reqs = [{"prompt_len": 1000, "token_times": [0.0, 10.0], "done": 10.0}
            for _ in range(slots)]
    return {"config": config(name), "peaks": {"hbm_bytes_per_s": 819e9},
            "load": {"requests": reqs},
            "trace": {"t_start": 1.0, "t_stop": 4.0, "ops": ops}}


@pytest.mark.parametrize("name,slots,ms,want", [
    # 128 slots x 4 MiB read and written: 1.074 GB, 1.311 ms at 819 GB/s
    ("nemotron-3-super-ep4-l11", 128, 1.5, 87.4),
    # 64 slots x 4 MiB: 0.537 GB, 0.655 ms
    ("granite-4.0-h-small-ep2-l10", 64, 0.8, 81.9),
])
def test_reader_on_a_recorded_run(name, slots, ms, want):
    read = common.load_file("layer_metrics", NAME).read
    # two numbered ops of the kernel, as a program with two loops holds
    # them, and a fusion whose name only contains the kernel's
    ops = {"ssm_step.7": (300, 300 * ms * 1e-3),
           "ssm_step.9": (100, 100 * ms * 1e-3),
           "ssm_step_gate_fusion.2": (400, 9.0),
           "pipelined_experts.3": (400, 0.8)}
    assert round(read(recorded(name, slots, ops)), 1) == want
    # half the slots live: half the bytes the step must move
    half = read(recorded(name, slots // 2, ops))
    assert round(half, 1) == round(want / 2, 1)


def test_reader_reads_nothing_where_no_such_op_ran():
    read = common.load_file("layer_metrics", NAME).read
    # the parent: the compiler's fusions walk the state
    parent = recorded("nemotron-3-super-ep4-l11", 128, {
        "add_dynamic-update-slice_fusion.2": (1200, 1.1),
        "multiply_reduce_fusion.2": (1200, 0.34)})
    assert read(parent) is None
    # a configuration with neither block, whatever its ops are called
    other = recorded("minicpm-sala-l12", 8, {"ssm_step.3": (100, 0.01)})
    assert read(other) is None
    assert read({"config": config("nemotron-3-super-ep4-l11")}) is None


def test_the_manifest_lists_the_two_recurrent_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_out_tokens_per_s",
        "workloads": [f"{c}.{t}" for c, t in CELLS.items()]}
    assert manifest["per_layer"][-1] is entry
