"""Force an 8-virtual-device CPU platform before JAX initializes a backend.

This is the TPU rebuild's equivalent of the reference's CPU/Gloo fake-cluster
path (reference README.md:40-47, train.py:83): every parallelism test runs as
a real multi-device program on one host. SURVEY.md §4 calls for exactly this.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# The run's persistent compilation cache (``utils.enable_compile_cache``:
# one fixed directory under the checkout, or JAX_COMPILATION_CACHE_DIR).
# ``_clear_jax_caches`` below drops every compiled program after every test
# and six workers build the same toy engines over and over, so most of the
# suite's time was compiling what had been compiled before (a file of 52
# engine tests: 287 s without, 226 s from an empty directory; PR 39, when
# the whole run took 1,438 s of the 1,470 it is given). The programs are
# the same bytes either way; tests/test_chip_compile.py turns the cache off
# around its described-chip compiles, which cannot be read back.
from picotron_tpu.utils import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)

import pytest  # noqa: E402

from picotron_tpu.config import Config  # noqa: E402


@pytest.fixture
def tiny_model_kwargs():
    """A tiny Llama config: GQA (8 q-heads, 4 kv-heads), small but
    tp/cp/pp-divisible everywhere."""
    return dict(
        num_hidden_layers=4,
        num_attention_heads=8,
        num_key_value_heads=4,
        hidden_size=64,
        intermediate_size=128,
        vocab_size=256,
        max_position_embeddings=128,
        rope_theta=10000.0,
        dtype="float32",
        attention_impl="sdpa",
    )


# Head geometries of the tiny model, for the serving cache's rows
# (inference/kv_cache.py::pack_factor: as many kv heads to a row as fill 128
# lanes). The tiny model itself has heads of 8 (a head a row: 4 kv heads
# are not 16); 256 / 4 gives heads of 64, two a row (SmolLM's); "d64gqa"
# two query heads a kv head, and under tp 2 ONE kv head a shard, which
# falls back to a head a row; heads of 32 lie four a row (two a shard under
# tp 2 do not: a head a row); a head of 128 is a row (Mistral's).
HEADS = {
    "d8": {},
    "d64": dict(hidden_size=256, num_attention_heads=4,
                num_key_value_heads=4),
    "d64gqa": dict(hidden_size=256, num_attention_heads=4,
                   num_key_value_heads=2),
    "d32": dict(hidden_size=128, num_attention_heads=4,
                num_key_value_heads=4),
    "d128": dict(hidden_size=256, num_attention_heads=2,
                 num_key_value_heads=2),
}
# heads a row on a 'tp' axis 1 and 2 wide
KV_PACK = {"d8": (1, 1), "d64": (2, 2), "d64gqa": (2, 1), "d32": (4, 1),
           "d128": (1, 1)}


def with_heads(tiny_model_kwargs, heads: str) -> dict:
    return {**tiny_model_kwargs, **HEADS[heads]}


def make_config(tiny_model_kwargs, dp=1, pp=1, cp=1, tp=1, seq=32, mbs=2, acc=1,
                engine="1f1b", dtype=None, zigzag=False, sp=False, zero1=False,
                cp_impl="ring", interleave=1, fsdp=False, stage_gating="auto",
                check_vma=False, **overrides) -> Config:
    raw = {
        "distributed": {"dp_size": dp, "pp_size": pp, "cp_size": cp, "tp_size": tp,
                        "pp_engine": engine, "use_cpu": True,
                        "cp_zigzag": zigzag, "tp_sequence_parallel": sp,
                        "zero1": zero1, "cp_impl": cp_impl,
                        "pp_interleave": interleave, "fsdp": fsdp,
                        "stage_gating": stage_gating, "check_vma": check_vma},
        "model": dict(tiny_model_kwargs, **({"dtype": dtype} if dtype else {})),
        "training": {**dict(seq_length=seq, micro_batch_size=mbs,
                            gradient_accumulation_steps=acc,
                            learning_rate=1e-3, remat="none"),
                     **overrides},
        "dataset": {"name": "synthetic"},
    }
    return Config.from_dict(raw)


@pytest.fixture
def cfg_factory(tiny_model_kwargs):
    def factory(**kw):
        return make_config(tiny_model_kwargs, **kw)

    return factory


@pytest.fixture(autouse=True)
def _clear_jax_caches():
    """XLA's CPU runtime aborts after ~17 live multi-device executables with
    collectives accumulate in-process; dropping compiled programs between
    tests keeps the suite stable (and bounds memory)."""
    yield
    jax.clear_caches()
