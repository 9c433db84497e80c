"""Operations and bytes of the Trinity block (``model_type: "afmoe"``) from
its shapes: what the algorithm needs, never what a program happens to
execute (a sliding layer needs the last ``sliding_window`` keys of a slot,
not the rows of whatever ring or prefix holds them). ``model`` is the
configuration file's dict of published keys, with ``num_experts`` the experts
held here of a router ``num_experts * ep_size`` wide, ``layer_types`` the
layers held and ``num_dense_layers`` the leading ones of them whose MLP is a
SwiGLU (``benchmarks/configs/trinity-large-ep32-l9.json``). Beside
``opcount.py``, which counts the dense block and is not edited.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes, head_dim

WINDOW, FULL = "sliding_attention", "full_attention"


def kind_counts(model: dict) -> tuple:
    """(sliding layers, full layers) held."""
    types = model["layer_types"]
    return types.count(WINDOW), types.count(FULL)


def params_by_part(model: dict) -> dict:
    """Parameters of one layer's parts, of the embedding and of the head."""
    H, D = model["hidden_size"], head_dim(model)
    nq = model["num_attention_heads"] * D
    nkv = model["num_key_value_heads"] * D
    I = model["moe_intermediate_size"]
    return {
        # W_q, W_o and the gate; W_k, W_v; the q and k norms
        "attention": 3 * H * nq + 2 * H * nkv + 2 * D,
        "norms": 4 * H,  # before and behind the attention and the MLP
        "dense_mlp": 3 * H * model["intermediate_size"],
        "router": H * model["num_experts"] * model["ep_size"],
        "router_bias": model["num_experts"] * model["ep_size"],
        "routed_expert": 3 * H * I,  # one of them: W1, W3, W2
        "shared_expert": 3 * H * I * model["num_shared_experts"],
        "embed": model["vocab_size"] * H,
        "head": H * model["vocab_size"],
        "final_norm": H,
    }


def layer_params(model: dict, dense: bool) -> int:
    """One layer: a leading ``dense`` one, or an expert layer with the
    routed experts held."""
    p = params_by_part(model)
    mlp = p["dense_mlp"] if dense else (
        p["router"] + p["router_bias"] + p["shared_expert"]
        + model["num_experts"] * p["routed_expert"])
    return p["attention"] + p["norms"] + mlp


def num_params(model: dict) -> int:
    p = params_by_part(model)
    dense = model["num_dense_layers"]
    return (p["embed"] + p["head"] + p["final_norm"]
            + dense * layer_params(model, True)
            + (model["num_hidden_layers"] - dense)
            * layer_params(model, False))


def kv_bytes_per_row(model: dict) -> int:
    """K and V of one token in one layer."""
    return (2 * model["num_key_value_heads"] * head_dim(model)
            * dtype_bytes(model))


def cache_bytes(model: dict, slots: int, max_seq_len: int,
                prefill_chunk: int) -> tuple:
    """(full layers' bytes, sliding layers' bytes) of the resident cache:
    ``max_seq_len`` rows a slot in a full layer, ``sliding_window +
    prefill_chunk`` in a sliding one."""
    n_window, n_full = kind_counts(model)
    ring = min(model["sliding_window"] + prefill_chunk, max_seq_len)
    row = kv_bytes_per_row(model) * slots
    return n_full * max_seq_len * row, n_window * ring * row


def window_rows(model: dict, contexts) -> float:
    """Rows one sliding layer must read for a step of slots holding
    ``contexts`` tokens each: the last ``sliding_window`` of each."""
    return float(sum(min(c, model["sliding_window"]) for c in contexts))


def window_attend_bytes(model: dict, contexts) -> float:
    """K and V one sliding layer must read for one decode step: what a
    kernel of the window attend is held to, whatever implements it."""
    return window_rows(model, contexts) * kv_bytes_per_row(model)


def decode_step_bytes(model: dict, contexts) -> float:
    """Least bytes of one decode step over slots that hold ``contexts``
    cached tokens each: every weight but the embedding table once (every
    held expert, as the share runs them: ``experts.routed_experts``), each
    live token's K and V in the full layers, the last ``sliding_window`` of
    each slot in the sliding ones."""
    n_window, n_full = kind_counts(model)
    weights = num_params(model) - params_by_part(model)["embed"]
    rows = n_full * float(sum(contexts)) + n_window * window_rows(
        model, contexts)
    return dtype_bytes(model) * weights + rows * kv_bytes_per_row(model)
