"""Operations and bytes of the MiniCPM-SALA block from its shapes: what the
algorithm needs, never what a program happens to execute (a slot that is
not live has no state a step must move, a key block the selection dropped
is not bytes a step must read). ``model`` is the configuration file's dict
of published keys, with ``mixer_types`` the layers held and
``sparse_config`` the sparse layers' sizes
(``benchmarks/configs/minicpm-sala-l12.json``). Beside ``opcount.py``, which
counts the dense block and is not edited.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes, head_dim


def kind_counts(model: dict) -> tuple:
    """(lightning layers, sparse layers) held."""
    types = model["mixer_types"]
    return types.count("lightning-attn"), types.count("minicpm4")


def lightning_width(model: dict) -> int:
    return model["lightning_nh"] * model["lightning_head_dim"]


def params_by_part(model: dict) -> dict:
    """Parameters of one layer's parts, of the embedding and of the untied
    head."""
    H, I, D = model["hidden_size"], model["intermediate_size"], head_dim(model)
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    Dl = lightning_width(model)
    return {
        # W_q, W_k, W_v, the output gate, W_o; the q and k norms a head's
        # width, the output norm, the slopes
        "lightning": 5 * H * Dl + 2 * model["lightning_head_dim"] + Dl
        + model["lightning_nh"],
        # W_q, W_o and the output gate at the query heads' width; W_k, W_v
        "sparse": 3 * H * nh * D + 2 * H * nkv * D,
        "norms": 2 * H,  # before the mixer, before the SwiGLU
        "mlp": 3 * H * I,
        "embed": model["vocab_size"] * H,
        "head": model["vocab_size"] * H,
        "final_norm": H,
    }


def layer_params(model: dict, kind: str) -> int:
    """One layer of ``kind`` ("lightning" | "sparse") with its SwiGLU."""
    p = params_by_part(model)
    return p[kind] + p["norms"] + p["mlp"]


def num_params(model: dict) -> int:
    p = params_by_part(model)
    n_light, n_sparse = kind_counts(model)
    return (p["embed"] + p["head"] + p["final_norm"]
            + n_light * layer_params(model, "lightning")
            + n_sparse * layer_params(model, "sparse"))


def state_bytes_per_slot(model: dict) -> int:
    """One sequence's float32 state over the lightning layers held,
    whatever its length."""
    n_light, _ = kind_counts(model)
    return 4 * n_light * model["lightning_nh"] \
        * model["lightning_head_dim"] ** 2


def kv_bytes_per_token(model: dict) -> int:
    """K and V of one token over the sparse layers held."""
    _, n_sparse = kind_counts(model)
    return (2 * n_sparse * model["num_key_value_heads"] * head_dim(model)
            * dtype_bytes(model))


def compressed_bytes_per_token(model: dict) -> float:
    """The compressed keys' share of one token over the sparse layers held:
    one row of the kv heads' width every ``kernel_stride`` tokens."""
    return kv_bytes_per_token(model) / 2 \
        / model["sparse_config"]["kernel_stride"]


def decode_step_bytes(model: dict, live_slots: float,
                      live_tokens: float) -> float:
    """Least bytes of one decode step over ``live_slots`` sequences that
    hold ``live_tokens`` cached tokens together: every weight but the
    embedding table once, each live slot's state read and written, the
    compressed keys of the live context, and K and V of the rows the
    selection keeps: ``topk`` blocks a slot, or its whole context while
    that is shorter."""
    sc = model["sparse_config"]
    kept = sc["topk"] * sc["block_size"]
    context = live_tokens / live_slots if live_slots else 0.0
    weights = num_params(model) - params_by_part(model)["embed"]
    return (dtype_bytes(model) * weights
            + 2 * live_slots * state_bytes_per_slot(model)
            + live_tokens * compressed_bytes_per_token(model)
            + live_slots * min(context, kept) * kv_bytes_per_token(model))
