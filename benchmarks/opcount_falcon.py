"""Operations and bytes of the Falcon-H1 block from its shapes: what the
algorithm needs, never what a program happens to execute (a slot that is not
live has no state a step must move, the window's dead keys are not bytes a
step must read). ``model`` is the configuration file's dict of published keys
(``benchmarks/configs/falcon-h1-34b-l4.json``): every layer holds BOTH
mixers, so the state and the K/V alike run over ``num_hidden_layers``.
Beside ``opcount.py``, which counts the dense block and is not edited.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes


def conv_width(model: dict) -> int:
    return model["mamba_d_ssm"] + 2 * model["mamba_n_groups"] \
        * model["mamba_d_state"]


def params_by_part(model: dict) -> dict:
    """Parameters of one layer's parts, and of the embedding and the head
    (untied)."""
    H, D = model["hidden_size"], model["head_dim"]
    Di, W, nh = model["mamba_d_ssm"], conv_width(model), model["mamba_n_heads"]
    return {
        # W_q, W_o, W_k, W_v
        "attention": (2 * H * model["num_attention_heads"] * D
                      + 2 * H * model["num_key_value_heads"] * D),
        # W_in (z | x B C | dt), W_out, the conv's taps and bias, dt_bias,
        # A_log and D a head, the gated norm
        "mamba": (H * (Di + W + nh) + Di * H + W * model["mamba_d_conv"] + W
                  + 3 * nh + Di),
        "mlp": 3 * H * model["intermediate_size"],
        "norms": 2 * H,
        "embed": model["vocab_size"] * H,
        "head": model["vocab_size"] * H,
        "final_norm": H,
    }


def layer_params(model: dict) -> int:
    p = params_by_part(model)
    return p["attention"] + p["mamba"] + p["mlp"] + p["norms"]


def num_params(model: dict) -> int:
    p = params_by_part(model)
    return (model["num_hidden_layers"] * layer_params(model) + p["embed"]
            + p["head"] + p["final_norm"])


def layer_state_bytes(model: dict) -> int:
    """One sequence's float32 state in one layer."""
    return 4 * model["mamba_d_ssm"] * model["mamba_d_state"]


def state_bytes_per_slot(model: dict) -> int:
    """One sequence's recurrent state over the layers held, whatever its
    length: the float32 state and the conv's last inputs."""
    return model["num_hidden_layers"] * (
        layer_state_bytes(model) + dtype_bytes(model)
        * (model["mamba_d_conv"] - 1) * conv_width(model))


def layer_kv_bytes_per_token(model: dict) -> int:
    """K and V of one token in one layer."""
    return (2 * model["num_key_value_heads"] * model["head_dim"]
            * dtype_bytes(model))


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token over the layers held (every one attends)."""
    return model["num_hidden_layers"] * layer_kv_bytes_per_token(model)


def decode_step_bytes(model: dict, live_slots: float,
                      live_tokens: float) -> float:
    """Least bytes of one decode step over ``live_slots`` sequences that
    hold ``live_tokens`` cached tokens together: every weight but the
    embedding table once (the head whole), each live slot's state read and
    written, each live token's K and V."""
    weights = num_params(model) - params_by_part(model)["embed"]
    return (dtype_bytes(model) * weights
            + 2 * live_slots * state_bytes_per_slot(model)
            + live_tokens * kv_bytes_per_token(model))
