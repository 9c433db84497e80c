"""What the two blocks on the Qwen3-MoE convention (``keye_vl2``,
``sdar_moe``) share: GQA projections whose queries and keys are RMS-normed a
head, a softmax router renormalised over its choice that hands its experts'
part to ``models/experts.py``, the checks on the keys both publish, and the
seeded tree both draw. What differs stays in the block: which keys a query
sees, how a head is rotated, what the cache holds.

``qkv`` and ``expert_mlp`` take the norm and the scoring function from their
caller, which names them in its own module: the controls put a fault there
(benchmarks/tests/control_keye.py, control_sdar.py)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.models import experts as expert_share
from picotron_tpu.models import support

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def router_width(m: ModelConfig) -> int:
    return m.num_experts * m.ep_size


def validate_experts(cfg: Config) -> None:
    """The checks on the expert keys both blocks publish: the share held,
    the router's width said twice, where the layers held lie, and the one
    value of each key that is implemented."""
    m = cfg.model
    support.positive(m, "num_experts", "num_experts_per_tok",
                     "moe_intermediate_size", "ep_size")
    support.ep_share(m, "num_experts")
    width = router_width(m)
    support.check(m, (
        m.num_local_experts not in (0, width),
        f"num_local_experts {m.num_local_experts} is not the router's width "
        f"{width} (num_experts x ep_size), which it repeats as published"))
    support.held_layers(m, m.num_hidden_layers)
    support.pinned(m, norm_topk_prob=True, decoder_sparse_step=1,
                   tie_word_embeddings=False)
    if m.mlp_only_layers:
        raise ValueError(
            f"{support.who(m)} implements model.mlp_only_layers = [] only "
            f"(got {m.mlp_only_layers!r}): every layer's MLP is the routed "
            "experts")


def attention_shapes(m: ModelConfig) -> dict:
    """The attention's matmul leaves of one layer, (in, out)."""
    H, hd = m.hidden_size, m.head_dim
    nh, nkv = m.num_attention_heads, m.num_key_value_heads
    return {"wq": (H, nh * hd), "wk": (H, nkv * hd), "wv": (H, nkv * hd),
            "wo": (nh * hd, H)}


def expert_shapes(m: ModelConfig) -> dict:
    """The router's and the held experts' leaves of one layer; the routed
    experts lead with the experts held."""
    H, E, I = m.hidden_size, m.num_experts, m.moe_intermediate_size
    return {"router": (H, router_width(m)),
            "w1": (E, H, I), "w3": (E, H, I), "w2": (E, I, H)}


def layers_key(key):
    """The key ``draw_tree`` folds the layers' leaves from (a block's own
    further leaves take the places behind ``shapes``)."""
    return jax.random.fold_in(key, 2)


def draw_tree(key, m: ModelConfig, shapes: dict, norms: dict,
              gains: dict) -> dict:
    """The global parameter pytree from ``key``: the layers' matmul leaves
    ``shapes`` U(+-gain * sqrt(1 / fan_in)) (``gains``, else 1) drawn in the
    model's dtype, each under the key of its place among the sorted names;
    the layers' norm vectors ``norms`` (name: width) ones; the embedding N(0,
    1), the final norm ones, the untied head U(+-sqrt(1 / hidden))."""
    dt = jnp.dtype(m.dtype)
    H, V, n = m.hidden_size, m.vocab_size, m.num_hidden_layers

    def uniform(k, shape, fan_in, gain=1.0):
        bound = gain * math.sqrt(1.0 / fan_in)
        return jax.random.uniform(k, shape, dt, -bound, bound)

    gkey = layers_key(key)
    layers = {name: jnp.ones((n, w), dt) for name, w in norms.items()}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        layers[name] = uniform(jax.random.fold_in(gkey, i), (n,) + shape,
                               shape[-2], gains.get(name, 1.0))
    return {
        "embed": jax.random.normal(jax.random.fold_in(key, 0), (V, H),
                                   F32).astype(dt),
        "final_norm": jnp.ones((H,), dt),
        "lm_head": uniform(jax.random.fold_in(key, 1), (H, V), H),
        "layers": layers,
    }


def qkv(lp, x, m: ModelConfig, norm) -> tuple:
    """(q [B, S, heads, head_dim], k, v [B, S, kv heads, head_dim]) of the
    normed stream ``x`` [B, S, H]: ``q`` and ``k`` normed a head by ``norm``
    (``rms_norm``, one weight vector each), not yet rotated."""
    B, S, _ = x.shape
    nh, nkv, hd = m.num_attention_heads, m.num_key_value_heads, m.head_dim
    eps = m.rms_norm_eps
    q = norm((x @ lp["wq"]).reshape(B, S, nh, hd), lp["q_norm"], eps)
    k = norm((x @ lp["wk"]).reshape(B, S, nkv, hd), lp["k_norm"], eps)
    return q, k, (x @ lp["wv"]).reshape(B, S, nkv, hd)


def expert_mlp(lp, x, m: ModelConfig, live, scores, scope: str) -> tuple:
    """The expert half of a layer on the normed stream ``x`` [B, S, H]:
    (this chip's part of the routed sum, what ``models/experts.py::share``
    counted). The router's logits in float32, ``scores`` of them (a softmax
    over the router's whole width), the ``num_experts_per_tok`` largest
    renormalised to sum 1; rows that are not ``live`` are routed nowhere.
    ``scope`` names the router's part of the trace."""
    B, S, H = x.shape
    x2 = x.reshape(B * S, H)
    with jax.named_scope(scope):
        logits = jnp.dot(x2.astype(F32), lp["router"].astype(F32),
                         precision=HIGHEST)
        experts, weights = expert_share.route(
            scores(logits), jnp.zeros((), F32),
            k=m.num_experts_per_tok, scale=1.0)
        w_held = expert_share.held_weights(
            experts, weights, m.ep_rank * m.num_experts, m.num_experts) \
            * live.reshape(B * S, 1).astype(F32)
    y, counted = expert_share.share(lp, x2, w_held)
    return y.reshape(B, S, H), counted
