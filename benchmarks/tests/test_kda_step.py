"""``kernels.kda_step_roofline`` (PR 59), a new case beside the files that
exist: the reader on a recorded run of the cell that lists it, on half the
slots, on the parent's fusions and on another configuration. By hand
(``python -m pytest benchmarks/tests/test_kda_step.py``)."""

import json
import os

from benchmarks import common

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NAME = "kernels.kda_step_roofline"
SOLAR = "solar-open2-ep16-l8"
CELL = SOLAR + ".serve-reasoning-decode"


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


# two numbered ops of the kernel, as the program's two runs of three KDA
# layers hold them, a fusion whose name only contains the kernel's, and the
# sister recurrence's kernel
OPS = {"kda_step.18": (300, 300 * 0.8e-3), "kda_step.19": (300, 300 * 0.8e-3),
       "kda_step_gate_fusion.2": (600, 9.0), "ssm_step.7": (600, 0.5)}


def test_reader_on_a_recorded_run():
    read = common.load_file("layer_metrics", NAME).read
    # 64 slots x 64 heads x 128 x 128 x 4 B read and written: 0.537 GB,
    # 0.6555 ms at 819 GB/s, against 0.8 ms a call
    assert round(read(recorded(SOLAR, 64, OPS)), 1) == 81.9
    # half the slots live: half the bytes the step must move
    assert round(read(recorded(SOLAR, 32, OPS)), 1) == 41.0


def test_reader_reads_nothing_where_no_such_op_ran():
    read = common.load_file("layer_metrics", NAME).read
    # the parent: the compiler's fusions walk the state
    parent = recorded(SOLAR, 64, {
        "add_dynamic-update-slice_fusion.4": (300, 0.31),
        "multiply_reduce_fusion.18": (300, 0.17)})
    assert read(parent) is None
    # a configuration without the block, whatever its ops are called
    other = recorded("nemotron-3-super-ep4-l11", 128, OPS)
    assert read(other) is None
    assert read({"config": config(SOLAR)}) is None


def test_the_manifest_lists_the_solar_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry, = [m for m in manifest["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "serve_out_tokens_per_s", "workloads": [CELL]}
    # appended behind what PR 58 left last (a later PR appends behind it)
    names = [m["name"] for m in manifest["per_layer"]]
    assert names.index(NAME) == \
        names.index("kernels.flash_decode_roofline.solar") + 1
