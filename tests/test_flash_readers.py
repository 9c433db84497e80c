"""The flash kernels' roofline readers on a hand-made trace reduction: the
backward reader (PR 33) counts the five matmuls a layer's backward needs
over the time of the ``flash_bwd_dkv`` and ``flash_bwd_dq`` events a
``flash_bwd_dkv`` event,
whether dQ has a kernel of its own (the tree before PR 33) or not."""

import json
import os

import pytest

from benchmarks.run import load_reader

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(ops):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "smollm-1.7b.json")) as f:
        config = json.load(f)
    return {"trace": {"ops": ops}, "steps": [0.7] * 4, "config": config,
            "peaks": {"bf16_flops_per_s": 197e12},
            "traffic": {"seq_length": 2048, "micro_batch_size": 3,
                        "distributed": {}}}


# one layer's backward at micro-batch 3 of SmolLM at 2048: 2.5 x 2 * S^2 * D
# * heads * 3 FLOPs = 128.8 GFLOP, 0.654 ms at the bf16 peak
LEAST_MS = 2.5 * 2 * 2048 ** 2 * 64 * 32 * 3 / 197e12 * 1e3


@pytest.mark.parametrize("ops,ms", [
    # two kernels a layer (1.310 + 2.155 ms: the ledger's PR 32 tail)
    ({"flash_bwd_dq.11": [96, 96 * 1.310e-3],
      "flash_bwd_dkv.11": [96, 96 * 2.155e-3],
      "flash_fwd.17": [96, 0.12], "fusion.3": [96, 0.14]}, 3.465),
    # the names the pipeline engine's explicit vjp gives the same kernels
    ({"transpose_jvp_flash_bwd_dq__.2": [64, 64 * 1.310e-3],
      "transpose_jvp_flash_bwd_dkv__.2": [64, 64 * 2.155e-3],
      "jvp_flash_fwd_.4": [64, 0.08]}, 3.465),
    # one kernel a layer, under two of the compiler's numberings
    ({"flash_bwd_dkv.3": [48, 48 * 2.0e-3], "flash_bwd_dkv": [48, 48 * 2.2e-3],
      "flash_fwd.17": [192, 0.24]}, 2.1),
    # a kernel is named by the END of the op's name: an op that merely holds
    # ``flash_bwd`` is not summed into the backward's time
    ({"flash_bwd_dkv.3": [96, 96 * 2.1e-3],
      "flash_bwd_dkv_operands_fusion.2": [96, 0.5],
      "copy_flash_bwd.1": [96, 0.5]}, 2.1),
])
def test_flash_bwd_roofline_reads_a_layers_backward(ops, ms):
    read = load_reader("layer_metrics", "kernels.flash_bwd_roofline")
    assert read(_run(ops)) == pytest.approx(100 * LEAST_MS / ms, rel=1e-6)
    assert 0 < read(_run(ops)) < 100


@pytest.mark.parametrize("run", [
    {}, {"trace": None},
    _run({"flash_fwd.17": [96, 0.12]}),  # a program with no backward kernel
])
def test_flash_bwd_roofline_finds_nothing(run):
    assert load_reader("layer_metrics", "kernels.flash_bwd_roofline")(run) \
        is None


def test_flash_bwd_roofline_is_listed_for_the_training_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    entry = by_name["kernels.flash_bwd_roofline"]
    fwd = by_name["kernels.flash_fwd_roofline"]
    assert entry == {**fwd, "name": "kernels.flash_bwd_roofline"}


# ---- the flash-decode kernel's share of the HBM roofline (PR 36) -----------


def _serve_run(ops, config="smollm-1.7b"):
    """A traced tail of 1 s over one stream that holds 1,000 prompt tokens
    and the first of its own."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config + ".json")) as f:
        config = json.load(f)
    requests = [{"prompt_len": 1000, "token_times": [0.5, 9.0], "done": 9.0},
                {"prompt_len": 500, "token_times": [], "done": 0.2}]
    return {"trace": {"ops": ops, "t_start": 1.0, "t_stop": 2.0},
            "load": {"requests": requests}, "config": config,
            "peaks": {"hbm_bytes_per_s": 819e9}}


# K and V of 1,001 live tokens, one of SmolLM's 24 layers: 2 x 32 heads x 64
# x 2 bytes a token = 8,192 bytes: 8.2 MB, 10.01 us at 819 GB/s
DECODE_LEAST_US = 1001 * 8192 / 819e9 * 1e6


@pytest.mark.parametrize("name", ["kernels.flash_decode_roofline",
                                  "kernels.flash_decode_roofline.chat"])
@pytest.mark.parametrize("ops,us", [
    ({"flash_decode_attention.6": [192, 192 * 25e-6],
      "fusion.198": [192, 0.3]}, 25.0),
    # two numberings of the one kernel; an op that merely holds its name
    # (a fusion of its operands) is not the kernel
    ({"flash_decode_attention.6": [96, 96 * 20e-6],
      "flash_decode_attention": [96, 96 * 30e-6],
      "flash_decode_attention_operands_fusion.2": [192, 0.5]}, 25.0),
])
def test_flash_decode_roofline_reads_a_layers_attend(name, ops, us):
    got = load_reader("layer_metrics", name)(_serve_run(ops))
    assert got == pytest.approx(100 * DECODE_LEAST_US / us, rel=1e-6)
    assert 0 < got < 100


@pytest.mark.parametrize("run", [
    {}, {"trace": None},
    # a program that attends densely: the parent, or a fall-back
    _serve_run({"dynamic-slice_bitcast_fusion.4": [192, 0.28],
                "fusion.198": [192, 0.3]}),
])
def test_flash_decode_roofline_finds_nothing(run):
    for name in ("kernels.flash_decode_roofline",
                 "kernels.flash_decode_roofline.chat"):
        assert load_reader("layer_metrics", name)(run) is None


def test_flash_decode_roofline_is_listed_for_the_llama_serving_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    base = {"unit": "%", "better": "higher", "source": "device_trace",
            "layer": "kernels"}
    assert by_name["kernels.flash_decode_roofline"] == {
        **base, "name": "kernels.flash_decode_roofline",
        "moves": "serve_out_tokens_per_s",
        "workloads": ["smollm-1.7b.serve-batch"]}
    assert by_name["kernels.flash_decode_roofline.chat"] == {
        **base, "name": "kernels.flash_decode_roofline.chat",
        "moves": "serve_tpot_mean_ms",
        "workloads": ["mistral-7b-v0.3-l16.serve-chat",
                      "mistral-7b-v0.3-l16.serve-longdoc"]}
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index("kernels.flash_decode_roofline")
    assert names[at + 1] == "kernels.flash_decode_roofline.chat"
