"""Operations and bytes of the DeepSeek-V3.2 block from its shapes: what the
algorithm needs, never what a program happens to execute (a padded cache
lane, the window's dead keys and an expert no token chose are not bytes a
step must read). ``model`` is the configuration file's dict of published
keys, with ``n_routed_experts`` the experts held here of a router
``n_routed_experts * ep_size`` wide (``benchmarks/configs/
deepseek-v3.2-ep32-l7.json``). Beside ``opcount.py``, which counts the dense
block and is not edited.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes


def params_by_part(model: dict) -> dict:
    """Parameters of one layer's parts, and of the embedding and the head."""
    H, nh = model["hidden_size"], model["num_attention_heads"]
    Rq, Rkv = model["q_lora_rank"], model["kv_lora_rank"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    ih, idim = model["index_n_heads"], model["index_head_dim"]
    I = model["moe_intermediate_size"]
    return {
        # W_qa, W_qb, W_kva, W_kvb, W_o and the three norms
        "attention": (H * Rq + Rq * nh * (dn + dr) + H * (Rkv + dr)
                      + Rkv * nh * (dn + dv) + nh * dv * H + H + Rq + Rkv),
        # W^I_qb, W^I_k, W^I_w and the LayerNorm's weight and bias
        "indexer": Rq * ih * idim + H * idim + H * ih + 2 * idim,
        "mlp_norm": H,
        "dense_mlp": 3 * H * model["intermediate_size"],
        "router": H * model["n_routed_experts"] * model["ep_size"],
        "router_bias": model["n_routed_experts"] * model["ep_size"],
        "routed_expert": 3 * H * I,  # one of them
        "shared_experts": model["n_shared_experts"] * 3 * H * I,
        "embed": model["vocab_size"] * H,
        "head": H * model["vocab_size"] + H,
    }


def layer_counts(model: dict) -> tuple:
    """(leading dense layers, expert layers)."""
    k = model["first_k_dense_replace"]
    return k, model["num_hidden_layers"] - k


def num_params(model: dict) -> int:
    p = params_by_part(model)
    dense, moe = layer_counts(model)
    each = p["attention"] + p["indexer"] + p["mlp_norm"]
    return (p["embed"] + p["head"] + dense * (each + p["dense_mlp"])
            + moe * (each + p["router"] + p["router_bias"]
                     + p["shared_experts"]
                     + model["n_routed_experts"] * p["routed_expert"]))


def cache_bytes_per_token(model: dict) -> int:
    """One token's rows over all layers: the compressed K/V, the shared
    RoPE key and the indexer's key, unpadded."""
    return (model["num_hidden_layers"] * dtype_bytes(model)
            * (model["kv_lora_rank"] + model["qk_rope_head_dim"]
               + model["index_head_dim"]))


def decode_step_bytes(model: dict, live_tokens: float, selected_rows: float,
                      experts_hit: float) -> float:
    """Least bytes of one decode step: every weight outside the routed
    experts once (but the embedding: a gather of a few rows); of the routed
    experts held, the ``experts_hit`` an expert layer's step reaches on
    average; the indexer's key of each of the ``live_tokens`` cached in
    live slots; the latent rows (compressed K/V + RoPE key) each slot
    attends, ``selected_rows`` together (``min(context, index_topk)`` a
    slot); the last three per layer."""
    p = params_by_part(model)
    dense, moe = layer_counts(model)
    b = dtype_bytes(model)
    each = p["attention"] + p["indexer"] + p["mlp_norm"]
    weights = b * (p["head"] + dense * (each + p["dense_mlp"])
                   + moe * (each + p["router"] + p["shared_experts"]
                            + experts_hit * p["routed_expert"])) \
        + 4 * moe * p["router_bias"]
    L = model["num_hidden_layers"]
    index_keys = L * live_tokens * model["index_head_dim"] * b
    rows = L * selected_rows * b * (model["kv_lora_rank"]
                                    + model["qk_rope_head_dim"])
    return weights + index_keys + rows


def prefill_flops_per_token(model: dict, context: float) -> float:
    """FLOPs of one prompt token at ``context`` cached keys, through every
    layer: the projections and the experts a token reaches (2 a parameter:
    the shared expert, and ``num_experts_per_tok / ep_size`` of the held
    ones on average), the indexer's scores of every key, and attention over
    ``min(context, index_topk)`` rows in the latent space (scores over rank
    + rope, values over rank, per head)."""
    p = params_by_part(model)
    dense, moe = layer_counts(model)
    nh, R, dr = (model["num_attention_heads"], model["kv_lora_rank"],
                 model["qk_rope_head_dim"])
    each = p["attention"] + p["indexer"]
    held = model["num_experts_per_tok"] / model["ep_size"]
    matmuls = 2 * (dense * (each + p["dense_mlp"])
                   + moe * (each + p["router"] + p["shared_experts"]
                            + held * p["routed_expert"])
                   + p["head"])
    L = model["num_hidden_layers"]
    index = L * 2 * model["index_n_heads"] * model["index_head_dim"] * context
    rows = min(context, model["index_topk"])
    attend = L * 2 * nh * rows * ((R + dr) + R)
    return matmuls + index + attend
