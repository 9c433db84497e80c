"""The seams a later configuration comes in by, with no edit to a file that
exists: the model keys a configuration lists, the reference it names. What
this program or this benchmark lacks ends the run with exit code 2 and a
message, before any device work."""

import json
import os

import pytest

from benchmarks import common

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what the parent's model_section() gave for the two files (PR 27, frozen
# before the function changed): key for key, in this order
EXPECTED = {
    "smollm-1.7b": {
        "num_hidden_layers": 24, "num_attention_heads": 32,
        "num_key_value_heads": 32, "hidden_size": 2048,
        "intermediate_size": 8192, "vocab_size": 49152,
        "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
        "max_position_embeddings": 2048,
        "name": "HuggingFaceTB/SmolLM-1.7B", "dtype": "bfloat16"},
    "mistral-7b-v0.3-l16": {
        "num_hidden_layers": 16, "num_attention_heads": 32,
        "num_key_value_heads": 8, "hidden_size": 4096,
        "intermediate_size": 14336, "vocab_size": 32768,
        "rms_norm_eps": 1e-05, "rope_theta": 1000000.0,
        "max_position_embeddings": 32768,
        "name": "mistralai/Mistral-7B-v0.3", "dtype": "bfloat16"},
}


def config(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def refused(capsys, fn, *args):
    """The message of a call that must exit 2."""
    with pytest.raises(SystemExit) as e:
        fn(*args)
    assert e.value.code == 2
    return capsys.readouterr().err


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_model_section_is_the_parents_for_the_files_that_exist(name):
    got = common.model_section(config(name))
    assert got == EXPECTED[name]
    assert list(got) == list(EXPECTED[name])  # the file it writes: byte-equal


def test_a_listed_key_is_passed_after_the_nine_under_its_own_name():
    c = dict(config("smollm-1.7b"), model_keys=["flash_block_q"],
             flash_block_q=256)
    got = common.model_section(c)
    assert got["flash_block_q"] == 256
    assert list(got)[:10] == [*common.MODEL_KEYS, "flash_block_q"]


def test_a_listed_key_modelconfig_lacks_exits_2_and_is_named(capsys):
    c = dict(config("mistral-7b-v0.3-l16"), model_keys=["num_experts"],
             num_experts=64)
    err = refused(capsys, common.model_section, c)
    assert "'num_experts'" in err and "mistralai/Mistral-7B-v0.3" in err
    assert "ModelConfig cannot express it" in err


def test_a_listed_key_without_a_value_exits_2(capsys):
    c = dict(config("smollm-1.7b"), model_keys=["flash_block_q"])
    assert "'flash_block_q'" in refused(capsys, common.model_section, c)


@pytest.mark.parametrize("key,value", [("head_dim", 96),
                                       ("sliding_window", 4096)])
def test_head_dim_and_window_are_checked_unless_listed(capsys, key, value):
    c = dict(config("mistral-7b-v0.3-l16"), **{key: value})
    assert "ModelConfig" in refused(capsys, common.model_section, c)
    # listed, the key goes to ModelConfig as any other: which has no field
    err = refused(capsys, common.model_section, dict(c, model_keys=[key]))
    assert repr(key) in err and "cannot express it" in err


def test_the_reference_is_loaded_by_name(tmp_path, monkeypatch, capsys):
    from benchmarks.reference import dense_decoder

    ref = common.load_reference(config("smollm-1.7b"))  # absent: the default
    assert ref.__file__ == dense_decoder.__file__
    err = refused(capsys, common.load_reference,
                  dict(config("smollm-1.7b"), reference="sparse_experts"))
    assert "'sparse_experts'" in err and "HuggingFaceTB/SmolLM-1.7B" in err
    # a reference a later PR adds is a file, found by the name alone
    monkeypatch.setattr(common, "HERE", str(tmp_path))
    (tmp_path / "reference").mkdir()
    (tmp_path / "reference" / "toy-ref.py").write_text(
        "def forward_logits(params, tokens, config, device):\n"
        "    return 'logits'\n"
        "def loss(params, ids, targets, config, device):\n"
        "    return 'loss'\n")
    ref = common.load_reference({"name": "toy", "reference": "toy-ref"})
    assert ref.forward_logits(0, 0, 0, 0) == "logits"
    assert ref.loss(0, 0, 0, 0, 0) == "loss"
    (tmp_path / "reference" / "half.py").write_text("def loss(*a):\n    return 0\n")
    err = refused(capsys, common.load_reference,
                  {"name": "toy", "reference": "half"})
    assert "forward_logits" in err
