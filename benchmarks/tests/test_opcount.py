"""opcount against numbers worked by hand for both configurations."""

import json
import os

import pytest

from benchmarks import opcount

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)


def test_smollm_counts():
    m = config("smollm-1.7b")
    # layer: 4 * 2048*2048 (q, k, v, o at 32 heads of 64) + 3 * 2048*8192
    # + 2 norms of 2048 = 16,777,216 + 50,331,648 + 4,096
    assert opcount.layer_params(m) == 67_112_960
    # 24 layers + 2 * 49152*2048 (embedding, untied head) + final norm
    assert opcount.num_params(m) == 24 * 67_112_960 + 2 * 100_663_296 + 2048
    assert round(opcount.num_params(m) / 1e9, 2) == 1.81
    # K and V, 24 layers, 32 kv heads of 64, bf16: 196,608 B ("196 KB");
    # lane-padded to 128 on the chip it is twice that
    assert opcount.kv_bytes_per_token(m) == 2 * 24 * 32 * 64 * 2 == 196_608
    assert opcount.kv_bytes_per_token(m, lane=128) == 393_216


def test_mistral_l16_counts():
    m = config("mistral-7b-v0.3-l16")
    # q and o: 4096*4096 each; k and v: 4096*1024 each; mlp 3 * 4096*14336
    assert opcount.layer_params(m) == (2 * 16_777_216 + 2 * 4_194_304
                                       + 3 * 58_720_256 + 8192)
    assert round(opcount.layer_params(m) / 1e6, 1) == 218.1
    assert round(opcount.num_params(m) / 1e9, 2) == 3.76
    # 2 * 16 layers * 8 kv heads * 128 * 2 B = 64 KiB, no lane padding
    assert opcount.kv_bytes_per_token(m) == 65_536
    assert opcount.kv_bytes_per_token(m, lane=128) == 65_536
    # bf16 weights one decode step reads: all but the embedding, 7.25 GB
    assert round(opcount.decode_weight_bytes(m) / 1e9, 2) == 7.25


def test_train_flops_per_token():
    m = config("smollm-1.7b")
    n = opcount.num_params(m)
    assert opcount.train_flops_per_token(m, 2048) == \
        6 * n + 12 * 24 * 2048 * 2048
    # one causal forward: 2 matmuls * 2*S*S*D*heads / 2, per layer
    assert opcount.causal_attention_flops(m, 2048) == \
        24 * 2 * 2048 * 2048 * 64 * 32


def test_peaks_known_and_unknown_kind():
    p = opcount.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError):
        opcount.peaks("cpu")
