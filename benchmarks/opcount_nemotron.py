"""Operations and bytes of the Nemotron-H block from its shapes: what the
algorithm needs, never what a program happens to execute (a slot that is not
live has no state a step must move, the window's dead keys are not bytes a
step must read). ``model`` is the configuration file's dict of published
keys, with ``n_routed_experts`` the experts held here of a router
``n_routed_experts * ep_size`` wide and ``hybrid_override_pattern`` the
layers held, one letter each (``benchmarks/configs/
nemotron-3-super-ep4-l11.json``). Beside ``opcount.py``, which counts the
dense block and is not edited.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes


def d_inner(model: dict) -> int:
    return model["mamba_num_heads"] * model["mamba_head_dim"]


def conv_width(model: dict) -> int:
    return d_inner(model) + 2 * model["n_groups"] * model["ssm_state_size"]


def kind_counts(model: dict) -> dict:
    """{"M": Mamba-2 layers, "E": expert layers, "*": attention layers}."""
    pattern = model["hybrid_override_pattern"]
    return {k: pattern.count(k) for k in "ME*"}


def params_by_part(model: dict) -> dict:
    """Parameters of one layer of each kind, of its parts where a step
    reads them apart, and of the embedding and the head (untied)."""
    H, nh = model["hidden_size"], model["mamba_num_heads"]
    Di, W, K = d_inner(model), conv_width(model), model["conv_kernel"]
    D, L = model["head_dim"], model["moe_latent_size"]
    I, Is = (model["moe_intermediate_size"],
             model["moe_shared_expert_intermediate_size"])
    width = model["n_routed_experts"] * model["ep_size"]
    return {
        # W_in (z | x B C | dt), W_out, the conv's taps and bias, dt_bias,
        # A_log and D a head, the gated norm, the layer's norm
        "M": H * (Di + W + nh) + Di * H + W * K + W + 3 * nh + Di + H,
        # W_q, W_k, W_v, W_o, the layer's norm
        "*": (2 * H * model["num_attention_heads"] * D
              + 2 * H * model["num_key_value_heads"] * D + H),
        # the router and its correction bias, the latent's way in and out,
        # the shared expert, the layer's norm: an expert layer but its
        # routed experts
        "E": H * width + width + 2 * H * L + 2 * H * Is + H,
        "routed_expert": 2 * L * I,  # one of them: W1, W2
        "embed": model["vocab_size"] * H,
        "head": model["vocab_size"] * H,
        "final_norm": H,
    }


def num_params(model: dict) -> int:
    p, n = params_by_part(model), kind_counts(model)
    return (p["embed"] + p["head"] + p["final_norm"] + n["M"] * p["M"]
            + n["*"] * p["*"] + n["E"] * (
                p["E"] + model["n_routed_experts"] * p["routed_expert"]))


def state_bytes_per_slot(model: dict) -> int:
    """One sequence's recurrent state over the Mamba layers held, whatever
    its length: the float32 state and the conv's last inputs."""
    return kind_counts(model)["M"] * (
        4 * d_inner(model) * model["ssm_state_size"]
        + dtype_bytes(model) * (model["conv_kernel"] - 1) * conv_width(model))


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token over the attention layers held."""
    return (2 * kind_counts(model)["*"] * model["num_key_value_heads"]
            * model["head_dim"] * dtype_bytes(model))


def decode_step_bytes(model: dict, live_slots: float,
                      live_tokens: float) -> float:
    """Least bytes of one decode step over ``live_slots`` sequences that
    hold ``live_tokens`` cached tokens together: every weight but the
    embedding table once (every held expert whole: the share runs every held
    expert over every row below the ridge, and at the cell's 128 slots a
    step's 704 held assignments a layer reach 99.6 % of the 128 anyway),
    each live slot's state read and written, each live token's K and V."""
    weights = num_params(model) - params_by_part(model)["embed"]
    return (dtype_bytes(model) * weights
            + 2 * live_slots * state_bytes_per_slot(model)
            + live_tokens * kv_bytes_per_token(model))


def pipelined_pass_bytes(model: dict, rows: float) -> float:
    """Least bytes of one expert layer's routed share as one pass of every
    held expert over ``rows`` rows (``pipelined_experts``): the held
    experts' two matrices each, the rows' latent in (the model's dtype) and
    their float32 sum out, each row's float32 weights."""
    held, L = model["n_routed_experts"], model["moe_latent_size"]
    return (dtype_bytes(model) * held * params_by_part(model)["routed_expert"]
            + rows * (L * (dtype_bytes(model) + 4) + 4 * held))
