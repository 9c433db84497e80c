"""Operations and bytes of the SDAR-MoE block (``model_type: "sdar_moe"``)
from its shapes: what the algorithm needs, never what a program happens to
execute. ``model`` is the configuration file's dict of published keys, with
``num_experts`` the experts held here of a router ``num_experts * ep_size``
wide (``benchmarks/configs/sdar-30b-a3b-ep8-l12.json``). Beside
``opcount.py``, which counts the dense block and is not edited.

The unit of work is a FORWARD of every live slot's current block
(``block_length`` rows a slot: a denoise forward or a commit forward,
``engine._block_forward``). Whatever ``block_length`` is, a forward must read
every weight once and every live row of K and V once: the rows of a block
share their keys.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes


def params_by_part(model: dict) -> dict:
    """Parameters of one layer's parts, of the embedding and of the head."""
    H, hd = model["hidden_size"], model["head_dim"]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return {
        # W_q, W_k, W_v, W_o and the two norm vectors a head
        "attention": 2 * H * nh * hd + 2 * H * nkv * hd + 2 * hd,
        "router": H * model["num_experts"] * model["ep_size"],
        "norms": 2 * H,  # before the attention and before the experts
        "routed_expert": 3 * H * model["moe_intermediate_size"],  # one
        "embed": model["vocab_size"] * H,
        "head": H * model["vocab_size"],
        "final_norm": H,
    }


def layer_params(model: dict) -> int:
    """One layer as held here: attention, router, two norms and the
    ``num_experts`` routed experts held."""
    p = params_by_part(model)
    return (p["attention"] + p["router"] + p["norms"]
            + model["num_experts"] * p["routed_expert"])


def num_params(model: dict) -> int:
    p = params_by_part(model)
    return (p["embed"] + p["head"] + p["final_norm"]
            + model["num_hidden_layers"] * layer_params(model))


def kv_bytes_per_row(model: dict) -> int:
    """K and V of one token in one layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] \
        * dtype_bytes(model)


def cache_bytes(model: dict, slots: int, max_seq_len: int) -> int:
    """K and V of the resident cache."""
    return (model["num_hidden_layers"] * slots * max_seq_len
            * kv_bytes_per_row(model))


def layer_kv_bytes(model: dict, live_tokens: float) -> float:
    """K and V one layer's forward must read over slots that hold
    ``live_tokens`` cached tokens together: every live row once."""
    return live_tokens * kv_bytes_per_row(model)


def forward_bytes(model: dict, live_tokens: float) -> float:
    """Least bytes of one forward over slots that hold ``live_tokens``
    cached tokens together: every weight but the embedding table once
    (every held expert, as the share runs them below the ridge) and every
    live row of K and V in every layer once."""
    weights = num_params(model) - params_by_part(model)["embed"]
    return dtype_bytes(model) * weights \
        + model["num_hidden_layers"] * layer_kv_bytes(model, live_tokens)


def forward_flops(model: dict, rows: int, live_tokens: float) -> float:
    """Matmul FLOPs of one forward of ``rows`` rows in all (slots x
    ``block_length``) as the share runs it: the projections, every held
    expert over every row, the scores and values of ``block_length`` rows a
    slot against its live keys, the head."""
    p = params_by_part(model)
    H, hd, nh = model["hidden_size"], model["head_dim"], \
        model["num_attention_heads"]
    a_layer = 2 * rows * (p["attention"] - 2 * hd + p["router"]
                          + model["num_experts"] * p["routed_expert"]) \
        + 4 * model["block_length"] * live_tokens * nh * hd
    return model["num_hidden_layers"] * a_layer + 2 * rows * H \
        * model["vocab_size"]
