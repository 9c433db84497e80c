"""Operations and bytes of the Keye-VL-2.0 block (``model_type: "KeyeVL2"``)
from its shapes: what the algorithm needs, never what a program happens to
execute (a decode step needs the ``min(context, topk)`` chosen rows of K and
V, not every live row a masked walk would read). ``model`` is the
configuration file's dict of published keys, with ``num_experts`` the experts
held here of a router ``num_experts * ep_size`` wide
(``benchmarks/configs/keye-vl-2.0-ep8-l12.json``). Beside ``opcount.py``,
which counts the dense block and is not edited.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes


def params_by_part(model: dict) -> dict:
    """Parameters of one layer's parts, of the embedding and of the head."""
    H, hd = model["hidden_size"], model["head_dim"]
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    sa = model["sa_config"]
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {
        # W_q, W_k, W_v, W_o and the two norm vectors a head
        "attention": 2 * H * nh * hd + 2 * H * nkv * hd + 2 * hd,
        # W^I_q, W^I_k, W^I_w and the LayerNorm's weight and bias
        "indexer": H * ih * idim + H * idim + H * ih + 2 * idim,
        "router": H * model["num_experts"] * model["ep_size"],
        "norms": 2 * H,  # before the attention and before the experts
        "routed_expert": 3 * H * model["moe_intermediate_size"],  # one
        "embed": model["vocab_size"] * H,
        "head": H * model["vocab_size"],
        "final_norm": H,
    }


def layer_params(model: dict) -> int:
    """One layer as held here: attention, indexer, router, two norms and the
    ``num_experts`` routed experts held."""
    p = params_by_part(model)
    return (p["attention"] + p["indexer"] + p["router"] + p["norms"]
            + model["num_experts"] * p["routed_expert"])


def num_params(model: dict) -> int:
    p = params_by_part(model)
    return (p["embed"] + p["head"] + p["final_norm"]
            + model["num_hidden_layers"] * layer_params(model))


def kv_bytes_per_row(model: dict) -> int:
    """K and V of one token in one layer."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] \
        * dtype_bytes(model)


def index_key_bytes(model: dict) -> int:
    """The indexer's key of one token in one layer, unpadded."""
    return model["sa_config"]["indexer_head_dim"] * dtype_bytes(model)


def cache_bytes(model: dict, slots: int, max_seq_len: int) -> tuple:
    """(K and V, the indexer's keys) of the resident cache."""
    rows = model["num_hidden_layers"] * slots * max_seq_len
    return rows * kv_bytes_per_row(model), rows * index_key_bytes(model)


def chosen_rows(model: dict, contexts) -> float:
    """Rows of K and V one layer's decode step must read over slots that
    hold ``contexts`` tokens each: ``min(context, topk)`` a slot."""
    topk = model["sa_config"]["topk"]
    return float(sum(min(c, topk) for c in contexts))


def decode_step_bytes(model: dict, contexts) -> float:
    """Least bytes of one decode step over slots that hold ``contexts``
    cached tokens each: every weight but the embedding table once (every
    held expert, as the share runs them: ``experts.routed_experts``), the
    indexer's key of every live token, and the chosen rows of K and V
    (``min(context, topk)`` a slot), the last two a layer."""
    weights = num_params(model) - params_by_part(model)["embed"]
    a_layer = (sum(contexts) * index_key_bytes(model)
               + chosen_rows(model, contexts) * kv_bytes_per_row(model))
    return dtype_bytes(model) * weights \
        + model["num_hidden_layers"] * a_layer
