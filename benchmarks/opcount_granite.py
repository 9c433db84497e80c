"""Operations and bytes of the Granite-4.0-H block from its shapes: what the
algorithm needs, never what a program happens to execute (a slot that is
not live has no state a step must move, the window's dead keys are not
bytes a step must read). ``model`` is the configuration file's dict of
published keys, with ``num_local_experts`` the experts held here of a router
``num_local_experts * ep_size`` wide and ``layer_types`` the layers held
(``benchmarks/configs/granite-4.0-h-small-ep2-l10.json``). Beside
``opcount.py``, which counts the dense block and is not edited.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes, head_dim


def d_inner(model: dict) -> int:
    return model["mamba_n_heads"] * model["mamba_d_head"]


def conv_width(model: dict) -> int:
    return d_inner(model) + 2 * model["mamba_n_groups"] * model["mamba_d_state"]


def kind_counts(model: dict) -> tuple:
    """(Mamba layers, attention layers) held."""
    types = model["layer_types"]
    return types.count("mamba"), types.count("attention")


def params_by_part(model: dict) -> dict:
    """Parameters of one layer's parts, and of the embedding (which is the
    head too: tied)."""
    H, nh = model["hidden_size"], model["mamba_n_heads"]
    Di, W, K = d_inner(model), conv_width(model), model["mamba_d_conv"]
    D = head_dim(model)
    I, Is = model["intermediate_size"], model["shared_intermediate_size"]
    return {
        # W_in (z | x B C | dt), W_out, the conv's taps and bias, dt_bias,
        # A_log and D a head, the gated norm
        "mamba": H * (Di + W + nh) + Di * H + W * K + W + 3 * nh + Di,
        # W_q, W_k, W_v, W_o
        "attention": (2 * H * model["num_attention_heads"] * D
                      + 2 * H * model["num_key_value_heads"] * D),
        "norms": 2 * H,  # before the mixer, before the experts
        "router": H * model["num_local_experts"] * model["ep_size"],
        "routed_expert": 3 * H * I,  # one of them: W1, W3, W2
        "shared_mlp": 3 * H * Is,
        "embed": model["vocab_size"] * H,
        "final_norm": H,
    }


def layer_params(model: dict, kind: str) -> int:
    """One layer of ``kind`` ("mamba" | "attention") with the routed
    experts held."""
    p = params_by_part(model)
    return (p[kind] + p["norms"] + p["router"] + p["shared_mlp"]
            + model["num_local_experts"] * p["routed_expert"])


def num_params(model: dict) -> int:
    p = params_by_part(model)
    n_mamba, n_attn = kind_counts(model)
    return (p["embed"] + p["final_norm"]
            + n_mamba * layer_params(model, "mamba")
            + n_attn * layer_params(model, "attention"))


def state_bytes_per_slot(model: dict) -> int:
    """One sequence's recurrent state over the Mamba layers held, whatever
    its length: the float32 state and the conv's last inputs."""
    n_mamba, _ = kind_counts(model)
    return n_mamba * (4 * d_inner(model) * model["mamba_d_state"]
                      + dtype_bytes(model) * (model["mamba_d_conv"] - 1)
                      * conv_width(model))


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token over the attention layers held."""
    _, n_attn = kind_counts(model)
    return (2 * n_attn * model["num_key_value_heads"] * head_dim(model)
            * dtype_bytes(model))


def decode_step_bytes(model: dict, live_slots: float,
                      live_tokens: float) -> float:
    """Least bytes of one decode step over ``live_slots`` sequences that
    hold ``live_tokens`` cached tokens together: every weight once (the
    embedding is the head; every held expert: at the cell's 64 slots a
    step's 320 held assignments a layer reach all 36), each live slot's
    state read and written, each live token's K and V."""
    return (dtype_bytes(model) * num_params(model)
            + 2 * live_slots * state_bytes_per_slot(model)
            + live_tokens * kv_bytes_per_token(model))


def ssd_scan_flops(model: dict, tokens: int) -> float:
    """FLOPs of the chunked scan over ``tokens`` rows of one Mamba layer, in
    chunks of ``mamba_chunk_size`` (the last one short): within a chunk of
    Q, ``C B^T`` and the masked product with ``dt x`` under the causal mask
    (half of the square each), the read-out of the incoming state and the
    state's update (2 Q d_inner d_state each)."""
    Q, N, Di = model["mamba_chunk_size"], model["mamba_d_state"], d_inner(model)
    total, left = 0.0, tokens
    while left > 0:
        q = min(Q, left)
        total += q * q * N + q * q * Di + 4 * q * Di * N
        left -= q
    return total


def prefill_flops_per_token(model: dict, context: float) -> float:
    """FLOPs one prompt token needs at ``context`` cached keys, through
    every layer held: 2 a parameter it reaches (the mixers, the router, the
    shared MLP, and ``num_experts_per_tok / ep_size`` of the held experts
    on average: the routed share, not every held expert), the scan's part
    of a full chunk, attention over ``context`` keys. The head is not a
    token's: ``head_flops``, once a prompt."""
    p = params_by_part(model)
    n_mamba, n_attn = kind_counts(model)
    routed = model["num_experts_per_tok"] / model["ep_size"]
    each = p["router"] + p["shared_mlp"] + routed * p["routed_expert"]
    Q = model["mamba_chunk_size"]
    scan = ssd_scan_flops(model, Q) / Q
    attend = 4 * model["num_attention_heads"] * head_dim(model) * context
    return (2 * (n_mamba * (p["mamba"] + each)
                 + n_attn * (p["attention"] + each))
            + n_mamba * scan + n_attn * attend)


def head_flops(model: dict) -> float:
    """The logits of one position: a prefill takes its last token's only."""
    return 2.0 * params_by_part(model)["embed"]
