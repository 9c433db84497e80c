"""Operations and bytes of the MiMo-V2 block (``model_type: "mimo_v2"``) from
its shapes: what the algorithm needs, never what a program happens to
execute (a sliding layer needs the last ``sliding_window`` keys of a slot,
not the rows of whatever ring holds them). ``model`` is the configuration
file's dict of published keys, with ``n_routed_experts`` the experts held
here of a router ``n_routed_experts * ep_size`` wide, and
``hybrid_layer_pattern`` (0 full, 1 sliding) and ``moe_layer_freq`` (0 SwiGLU,
1 experts) one entry a layer held
(``benchmarks/configs/mimo-v2.5-ep32-l13.json``). Beside ``opcount.py``, which
counts the dense block and is not edited.
"""

from __future__ import annotations

from benchmarks.opcount import dtype_bytes


def heads(model: dict, sliding: bool) -> tuple:
    """(query heads, K/V heads, key head's width, value head's width) of a
    kind of layer."""
    pre = "swa_" if sliding else ""
    return (model[pre + "num_attention_heads"],
            model[pre + "num_key_value_heads"], model[pre + "head_dim"],
            model[pre + "v_head_dim"])


def kind_counts(model: dict) -> tuple:
    """(sliding layers, full layers) held."""
    n = sum(model["hybrid_layer_pattern"])
    return n, len(model["hybrid_layer_pattern"]) - n


def attention_params(model: dict, sliding: bool) -> int:
    """W_q, W_k, W_v, W_o of a layer and, of a sliding one, a sink a head."""
    H = model["hidden_size"]
    nh, nkv, hd, vd = heads(model, sliding)
    sinks = nh if sliding and model["add_swa_attention_sink_bias"] else 0
    return H * nh * hd + H * nkv * hd + H * nkv * vd + nh * vd * H + sinks


def params_by_part(model: dict) -> dict:
    """Parameters of one layer's parts, of the embedding and of the head."""
    H, I = model["hidden_size"], model["moe_intermediate_size"]
    width = model["n_routed_experts"] * model["ep_size"]
    return {
        "full_attention": attention_params(model, False),
        "sliding_attention": attention_params(model, True),
        "norms": 2 * H,  # before the attention and before the MLP
        "dense_mlp": 3 * H * model["intermediate_size"],
        "router": H * width,
        "router_bias": width,
        "routed_expert": 3 * H * I,  # one of them: W1, W3, W2
        "embed": model["vocab_size"] * H,
        "head": H * model["vocab_size"],
        "final_norm": H,
    }


def layer_params(model: dict, sliding: bool, experts: bool) -> int:
    """One layer: its attention by kind, two norms, and a SwiGLU or a router
    with the routed experts held."""
    p = params_by_part(model)
    mlp = (p["router"] + p["router_bias"]
           + model["n_routed_experts"] * p["routed_expert"]) if experts \
        else p["dense_mlp"]
    return p["sliding_attention" if sliding else "full_attention"] \
        + p["norms"] + mlp


def num_params(model: dict) -> int:
    p = params_by_part(model)
    return p["embed"] + p["head"] + p["final_norm"] + sum(
        layer_params(model, bool(s), bool(e)) for s, e in zip(
            model["hybrid_layer_pattern"], model["moe_layer_freq"]))


def kv_bytes_per_row(model: dict, sliding: bool) -> int:
    """K and V of one token in one layer of a kind."""
    _, nkv, hd, vd = heads(model, sliding)
    return nkv * (hd + vd) * dtype_bytes(model)


def cache_bytes(model: dict, slots: int, max_seq_len: int,
                prefill_chunk: int) -> tuple:
    """(full layers' bytes, sliding layers' bytes) of the resident cache:
    ``max_seq_len`` rows a slot in a full layer, ``sliding_window +
    prefill_chunk`` in a sliding one."""
    n_window, n_full = kind_counts(model)
    ring = min(model["sliding_window"] + prefill_chunk, max_seq_len)
    return (n_full * slots * max_seq_len * kv_bytes_per_row(model, False),
            n_window * slots * ring * kv_bytes_per_row(model, True))


def window_rows(model: dict, contexts) -> float:
    """Rows one sliding layer must read for a step of slots holding
    ``contexts`` tokens each: the last ``sliding_window`` of each."""
    return float(sum(min(c, model["sliding_window"]) for c in contexts))


def window_attend_bytes(model: dict, contexts) -> float:
    """K and V one sliding layer must read for one decode step: what a
    kernel of the window attend is held to, whatever implements it."""
    return window_rows(model, contexts) * kv_bytes_per_row(model, True)


def full_attend_bytes(model: dict, contexts) -> float:
    """K and V one full layer must read for one decode step: every live
    token's."""
    return float(sum(contexts)) * kv_bytes_per_row(model, False)


def decode_step_bytes(model: dict, contexts) -> float:
    """Least bytes of one decode step over slots that hold ``contexts``
    cached tokens each: every weight but the embedding table once (every
    held expert, as the share runs them: ``experts.routed_experts``), each
    live token's K and V in the full layers, the last ``sliding_window`` of
    each slot in the sliding ones."""
    n_window, n_full = kind_counts(model)
    weights = num_params(model) - params_by_part(model)["embed"]
    return (dtype_bytes(model) * weights
            + n_full * full_attend_bytes(model, contexts)
            + n_window * window_attend_bytes(model, contexts))
