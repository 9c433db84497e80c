"""The benchmark's plain reference: a dense Llama-style decoder in jax.numpy.

Float32 throughout, ``jax.default_matmul_precision("highest")`` (on a TPU a
float32 matmul otherwise runs in bf16 passes), no kernels, no cache, no
batching tricks: RMSNorm, rotary embedding in the rotate-half convention the
program applies (``ops/rope.py``: angles tiled ``[a, a]``, ``x*cos +
[-x2, x1]*sin``), grouped-query causal attention, SwiGLU, final norm, untied
head, mean cross-entropy. It imports nothing from ``picotron_tpu``.

Departures from the published models, both the program's own and copied here
so the two can agree: the LM head is never tied to the embedding, and the
weights are the program's seeded random ones, stored ``[in, out]``.

Parameters come from the system under test *one layer at a time*
(``layer_of``): each layer's leaves are sliced from the stacked ``[L, ...]``
tree, put on one device (over ICI when the tree is sharded over four chips)
and cast to float32 inside the jitted block, so no second model is ever
resident beside the system's own.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def rope_tables(seq: int, head_dim: int, theta: float):
    """(cos, sin), each [seq, head_dim] float32, halves tiled."""
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                           / head_dim))
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w


def _rotate(x, cos, sin):
    """x: [S, heads, D]; cos/sin: [S, D]."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + rot * sin[:, None, :]


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "eps"))
def block(lp, h, cos, sin, *, n_heads: int, n_kv: int, eps: float):
    """One decoder block on one sequence. ``h``: [S, H] float32; ``lp``: the
    layer's leaves in whatever dtype the system stores (cast here)."""
    with jax.default_matmul_precision("highest"):
        lp = jax.tree.map(lambda v: v.astype(F32), lp)
        S, _ = h.shape
        D = lp["wq"].shape[-1] // n_heads
        x = _rms_norm(h, lp["attn_norm"], eps)
        q = _rotate((x @ lp["wq"]).reshape(S, n_heads, D), cos, sin)
        k = _rotate((x @ lp["wk"]).reshape(S, n_kv, D), cos, sin)
        v = (x @ lp["wv"]).reshape(S, n_kv, D)
        g = n_heads // n_kv
        q = q.reshape(S, n_kv, g, D).transpose(1, 2, 0, 3)  # [kv, g, S, D]
        k = k.transpose(1, 0, 2)  # [kv, S, D]
        v = v.transpose(1, 0, 2)
        causal = jnp.tril(jnp.ones((S, S), bool))

        def one_group(qkv):  # one kv head at a time bounds the [S, S] scores
            qg, kg, vg = qkv
            s = jnp.einsum("gsd,td->gst", qg, kg) / math.sqrt(D)
            s = jnp.where(causal[None], s, -jnp.inf)
            return jnp.einsum("gst,td->gsd", jax.nn.softmax(s, axis=-1), vg)

        o = jax.lax.map(one_group, (q, k, v))  # [kv, g, S, D]
        o = o.transpose(2, 0, 1, 3).reshape(S, n_heads * D)
        h = h + o @ lp["wo"]
        x = _rms_norm(h, lp["mlp_norm"], eps)
        return h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) \
            @ lp["w_down"]


@partial(jax.jit, static_argnames=("eps",))
def head(final_norm, lm_head, h, *, eps: float):
    """Final norm and the untied head: [S, H] -> logits [S, V] float32."""
    with jax.default_matmul_precision("highest"):
        return _rms_norm(h, final_norm.astype(F32), eps) \
            @ lm_head.astype(F32)


@jax.jit
def mean_cross_entropy(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], axis=-1))


def layer_of(params, i: int, device):
    """Layer ``i`` of the system's stacked tree, whole, on ``device``."""
    return jax.device_put(
        jax.tree.map(lambda v: v[i], params["layers"]), device)


def forward_logits(params, tokens, model: dict, device=None):
    """Logits [B, S, V] (numpy float32) of ``tokens`` [B, S] under the
    system's ``params`` (its own tree: ``embed`` [V, H], ``layers`` stacked
    [L, ...], ``final_norm``, ``lm_head`` [H, V]). ``model`` holds the
    published keys (``num_hidden_layers``, ``num_attention_heads``,
    ``num_key_value_heads``, ``rms_norm_eps``, ``rope_theta``)."""
    return np.stack([np.asarray(x) for x in
                     _per_sequence(params, tokens, model, device, None)])


def loss(params, tokens, targets, model: dict, device=None) -> float:
    """Mean next-token cross-entropy over every position of ``tokens``
    [B, S] against ``targets`` [B, S]: the mean of the sequences' means."""
    per = _per_sequence(params, tokens, model, device, np.asarray(targets))
    return float(np.mean([float(x) for x in per]))


def _per_sequence(params, tokens, model, device, targets):
    device = device or jax.devices()[0]
    tokens = np.asarray(tokens)
    n_heads = int(model["num_attention_heads"])
    n_kv = int(model["num_key_value_heads"])
    eps = float(model["rms_norm_eps"])
    L = int(model["num_hidden_layers"])
    S = tokens.shape[1]
    head_dim = params["layers"]["wq"].shape[-1] // n_heads
    cos, sin = (jax.device_put(t, device) for t in
                rope_tables(S, head_dim, float(model["rope_theta"])))
    # only the rows the tokens name leave the (possibly sharded) table
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          for t in tokens]
    for i in range(L):
        lp = layer_of(params, i, device)
        hs = [block(lp, h, cos, sin, n_heads=n_heads, n_kv=n_kv, eps=eps)
              for h in hs]
        del lp
        # one layer's copy at a time: dispatched ahead of a long sequence's
        # blocks, the next layers' copies would all be resident at once
        jax.block_until_ready(hs)
    fn = jax.device_put(params["final_norm"], device)
    lm = jax.device_put(params["lm_head"], device)
    out = []
    for b, h in enumerate(hs):
        logits = head(fn, lm, h, eps=eps)
        out.append(logits if targets is None else mean_cross_entropy(
            logits, jax.device_put(jnp.asarray(targets[b]), device)))
    return out
