"""Operations and bytes of the Solar Open 2 block from its shapes: what the
algorithm needs, never what a program happens to execute (a slot that is not
live has no state a step must move, the window's dead keys are not bytes a
step must read). ``model`` is the configuration file's dict of published
keys, with ``n_routed_experts`` the experts held here of a router
``n_routed_experts * ep_size`` wide and ``gqa_layers`` the held layers whose
mixer is the gated GQA (``benchmarks/configs/solar-open2-ep16-l8.json``).
Beside ``opcount.py``, which counts the dense block and is not edited.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes


def kda_width(model: dict) -> int:
    """Channels of a KDA layer's q, of its k, of its v: heads x head_dim."""
    la = model["linear_attn_config"]
    return la["num_heads"] * la["head_dim"]


def kind_counts(model: dict) -> dict:
    """{"gqa": gated GQA layers held, "kda": KDA layers held}."""
    gqa = len(model["gqa_layers"])
    return {"gqa": gqa, "kda": model["num_hidden_layers"] - gqa}


def params_by_part(model: dict) -> dict:
    """Parameters of one mixer of each kind, of a layer's expert half
    outside its routed experts, of one routed expert, and of the embedding
    and the head (untied)."""
    H, la = model["hidden_size"], model["linear_attn_config"]
    D, hd, nh = kda_width(model), la["head_dim"], la["num_heads"]
    q = model["num_attention_heads"] * model["head_dim"]
    kv = model["num_key_value_heads"] * model["head_dim"]
    I = model["moe_intermediate_size"]
    width = model["n_routed_experts"] * model["ep_size"]
    # the decay's and the gate's way to a head's width: one matrix, or
    # through a rank of head_dim
    narrow = H * D if model.get("kda_use_full_proj") else H * hd + hd * D
    return {
        # W_q | W_k | W_v, W_out, the decay's and the gate's projections,
        # W_b, the conv's taps, A_log a head, dt_bias, the head's norm
        "kda": (3 * H * D + D * H + 2 * narrow + H * nh
                + 3 * D * la["short_conv_kernel_size"] + nh + D + hd),
        # W_q, W_o, the gate, W_k, W_v
        "gqa": (2 + bool(model["use_gqa_gate"])) * H * q + 2 * H * kv,
        # the router and its correction bias, the shared expert, the
        # layer's two norms: an expert half but its routed experts
        "experts": (H * width + width
                    + 3 * H * I * model["n_shared_experts"] + 2 * H),
        "routed_expert": 3 * H * I,  # one of them: W1, W3, W2
        "embed": model["vocab_size"] * H,
        "head": model["vocab_size"] * H,
        "final_norm": H,
    }


def num_params(model: dict) -> int:
    p, n = params_by_part(model), kind_counts(model)
    return (p["embed"] + p["head"] + p["final_norm"] + n["kda"] * p["kda"]
            + n["gqa"] * p["gqa"] + model["num_hidden_layers"] * (
                p["experts"] + model["n_routed_experts"] * p["routed_expert"]))


def layer_state_bytes(model: dict) -> int:
    """One sequence's float32 state in one KDA layer: heads x keys x
    values."""
    return 4 * kda_width(model) * model["linear_attn_config"]["head_dim"]


def state_bytes_per_slot(model: dict) -> int:
    """One sequence's recurrent state over the KDA layers held, whatever
    its length: the float32 state and the three convs' last inputs."""
    taps = model["linear_attn_config"]["short_conv_kernel_size"]
    return kind_counts(model)["kda"] * (
        layer_state_bytes(model)
        + dtype_bytes(model) * (taps - 1) * 3 * kda_width(model))


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token over the GQA layers held."""
    return (2 * kind_counts(model)["gqa"] * model["num_key_value_heads"]
            * model["head_dim"] * dtype_bytes(model))


def decode_step_bytes(model: dict, live_slots: float,
                      live_tokens: float) -> float:
    """Least bytes of one decode step over ``live_slots`` sequences that
    hold ``live_tokens`` cached tokens together: every weight but the
    embedding table once (every held expert whole: below the ridge the
    share runs every held expert over every row, as in the deployment this
    is cut from, where each has rows at every step), each live slot's state
    and conv tails read and written, each live token's K and V."""
    weights = num_params(model) - params_by_part(model)["embed"]
    return (dtype_bytes(model) * weights
            + 2 * live_slots * state_bytes_per_slot(model)
            + live_tokens * kv_bytes_per_token(model))
