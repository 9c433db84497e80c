"""Operations and bytes from shapes, and the table of peaks.

The yardstick's arithmetic: what the algorithm needs, never what a program
happens to execute (recomputed FLOPs of ``remat`` do not count, a padded
cache lane is not a byte the step must read). ``model`` is a configuration
file's dict of published keys. Copied from ``picotron_tpu/utils.py``
(``flops_per_token``) and ``bench_decode.py`` (``kv_bytes_per_token``) so a
later PR cannot move them; the originals are listed in PERF.md.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """The peaks of one chip of ``device_kind``. An unknown kind is an
    error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks known for device_kind {device_kind!r}; "
                       f"benchmarks/peaks.json has {sorted(table)}")
    return table[device_kind]


def head_dim(model: dict) -> int:
    return int(model.get("head_dim")
               or model["hidden_size"] // model["num_attention_heads"])


def layer_params(model: dict) -> int:
    H, I, D = model["hidden_size"], model["intermediate_size"], head_dim(model)
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return H * nh * D + 2 * H * nkv * D + nh * D * H + 3 * H * I + 2 * H


def num_params(model: dict) -> int:
    """Embedding + layers + final norm + the untied head."""
    H, V = model["hidden_size"], model["vocab_size"]
    return V * H + model["num_hidden_layers"] * layer_params(model) + H + H * V


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """6N + 12*L*H*S: forward and backward of every parameter, plus the
    attention scores and values. What ``remat`` recomputes is not counted."""
    return (6 * num_params(model)
            + 12 * model["num_hidden_layers"] * model["hidden_size"] * seq_len)


def dtype_bytes(model: dict) -> int:
    return DTYPE_BYTES[model.get("torch_dtype", "bfloat16")]


def kv_bytes_per_token(model: dict, lane: int = 0) -> int:
    """K and V of one token over all layers. ``lane`` > 0 pads the head
    size up to a multiple of it (the chip tiles the minor dim to 128)."""
    D = head_dim(model)
    if lane:
        D = -(-D // lane) * lane
    return (2 * model["num_hidden_layers"] * model["num_key_value_heads"]
            * D * dtype_bytes(model))


def decode_weight_bytes(model: dict) -> int:
    """Weights one decode step must read: every layer, the final norm and
    the head. The embedding is a gather of a few rows and is left out."""
    H, V = model["hidden_size"], model["vocab_size"]
    return ((model["num_hidden_layers"] * layer_params(model) + H + H * V)
            * dtype_bytes(model))


def decode_step_bytes(model: dict, live_tokens: float) -> float:
    """Least bytes of one decode step over slots that hold ``live_tokens``
    cached tokens together: the weights once, each live token's K and V."""
    return decode_weight_bytes(model) + live_tokens * kv_bytes_per_token(model)


def causal_attention_flops(model: dict, seq_len: int, fwd_only: bool = True):
    """Score and value matmuls of one sequence through every layer under the
    causal mask (half of the square): 2 * 2*S^2*D*heads / 2 per layer."""
    per_layer = 2 * seq_len * seq_len * head_dim(model) \
        * model["num_attention_heads"]
    return model["num_hidden_layers"] * per_layer * (1 if fwd_only else 3)
