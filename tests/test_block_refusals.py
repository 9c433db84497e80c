"""What every block but Llama refuses, by name: the twelve things only the
Llama block does (``models/support.py`` words them once, a block's ``WHY``
gives its reason), each asked of each block's toy (``block_toys.py``) through
``Config.from_dict``. A block's checks of its own keys are its own test
file's (``test_validate_refuses_what_the_block_lacks``)."""

import pytest
from block_toys import TOYS, make_config

from picotron_tpu import models
from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.models import support

# a key of ``WHY`` -> (the sections that ask for it, what the refusal says)
ASKED = {
    "training": ({}, "served, not trained"),
    "tp": ({"distributed": {"tp_size": 2}}, "tp_size > 1"),
    "dp": ({"inference": {"dp_size": 2}}, "dp_size > 1"),
    "paged": ({"inference": {"kv_layout": "paged"}}, "kv_layout 'paged'"),
    "kv_int8": ({"inference": {"kv_cache_dtype": "int8"}},
                "kv_cache_dtype 'int8'"),
    "weight_int8": ({"inference": {"weight_dtype": "int8"}},
                    "weight_dtype 'int8'"),
    "lora": ({"inference": {"tenancy": {"tenants": [{"name": "a"}]}}},
             "LoRA"),
    "speculation": ({"inference": {"spec_len": 4}}, "speculation"),
    "flash": ({"inference": {"attend_impl": "flash"}}, "attend_impl 'flash'"),
    # 'slot', or the common check of the schedule (behind the block's) has
    # nothing to say of it
    "overlap": ({"inference": {"overlap": True, "key_schedule": "slot"}},
                "inference.overlap"),
    "mixed_dispatch": ({"inference": {"mixed_dispatch": True}},
                       "mixed_dispatch"),
    "key_schedule": ({"inference": {"key_schedule": "slot"}},
                     "key_schedule 'slot'"),
}
BLOCKS = [b for b in models.BLOCKS if b != "llama"]


@pytest.mark.parametrize("what", list(ASKED))
@pytest.mark.parametrize("block", BLOCKS)
def test_a_block_refuses_by_name(block, what):
    sections, says = ASKED[what]
    module = models.model_module(ModelConfig(model_type=block))
    assert module.WHY[what] and len(module.WHY) == len(ASKED)
    with pytest.raises(ValueError) as e:
        if what == "training":
            make_config(block).validate(for_training=True)
        else:
            make_config(block, **{k: dict(v) for k, v in sections.items()})
    message = str(e.value)
    assert message.startswith(f"model_type {block!r} ")
    assert says in message and module.WHY[what] in message


@pytest.mark.parametrize("block", BLOCKS)
def test_a_blocks_toy_validates_as_it_stands(block):
    cfg = make_config(block)
    cfg.validate()
    assert cfg.model.model_type == block
    assert support.who(cfg.model) == f"model_type {block!r}"


def test_the_table_of_blocks_is_the_list_validate_knows():
    """An unknown ``model_type`` is refused with every name of
    ``models.BLOCKS``; every module it names is there, and only the Llama
    block declares no ``validate``."""
    with pytest.raises(ValueError, match="unknown model_type 'gpt2'") as e:
        Config.from_dict({"model": {"model_type": "gpt2"}})
    assert all(name in str(e.value) for name in models.BLOCKS)
    assert set(TOYS) == set(BLOCKS)
    for block in models.BLOCKS:
        module = models.model_module(ModelConfig(model_type=block))
        assert hasattr(module, "validate") == (block != "llama")
