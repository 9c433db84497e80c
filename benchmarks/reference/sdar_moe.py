"""The plain reference of the SDAR-MoE block (``model_type: "sdar_moe"``,
SDAR-30B-A3B-Chat): a Qwen3-MoE-shaped decoder (GQA with q/k norm a head and
plain RoPE, softmax-routed experts with no shared one in every layer) that
attends block-causally and generates by diffusion over blocks, in jax.numpy.

Float32 throughout under ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching, and nothing imported from ``picotron_tpu``.
Written from the published description (the model's ``config.json`` and
SDAR's ``block_diffusion_generate``), not from ``models/sdar_moe.py``.

What it computes (``x`` the normed stream of one sequence; ``N`` RMSNorm
with weight, eps ``rms_norm_eps``; no bias in any projection; ``Bd =
block_length``):

- ``h = E[tokens]``; a layer: ``h += Attn(N1(h))``, then ``h += MoE(N2(h))``;
  ``logits = Nf(h) W_head``, untied. ``logits[i]`` scores the token AT
  position ``i``: a position not yet decided holds ``mask_token_id``;
- ``q = x W_q`` (``num_attention_heads`` of ``head_dim``), ``k = x W_k``, ``v
  = x W_v`` (``num_key_value_heads`` of ``head_dim``); ``q`` and ``k``
  RMS-normed a head (one weight vector each); RoPE on the whole head, halves
  paired (``x[i]``, ``x[i + head_dim / 2]``), pair ``i``'s angle ``p *
  theta^(-2i / head_dim)``, no scaling;
- key ``j`` is visible to query ``i`` iff ``j // Bd <= i // Bd``:
  bidirectional inside a block, causal between blocks;
  ``softmax(q . k / sqrt(head_dim))`` over the visible keys, times ``v``,
  through ``W_o``;
- ``s = softmax(x W_r)`` over the router's whole width; the
  ``num_experts_per_tok`` largest, ties to the lower index (a stable sort);
  weights ``s[chosen] / sum`` (``norm_topk_prob``); the sum over the chosen
  experts *held here* of ``w_e (silu(x W1_e) * (x W3_e)) W2_e``.

``generate`` is the published loop with no cache: the sequence is laid in
aligned blocks of ``Bd``; a block starts as what the prompt gives of it, then
``mask_token_id``; for step ``s = 0 .. denoising_steps - 1`` while a position
is masked, the whole sequence so far is forwarded, ``x0 = argmax`` (temperature
0), the confidence of a masked position is ``softmax(logits)[x0]`` and of any
other ``-inf``; ``n_s = Bd // T + (s < Bd % T)``; ``low_confidence_static``
unmasks the ``n_s`` masked positions of largest confidence,
``low_confidence_dynamic`` all those above ``confidence_threshold`` where at
least ``n_s`` are, else as static. The stream ends at the first EOS among a
finished block's new tokens, or at the budget.

Departures, the program's own and copied here so that the two can agree:

- the share: ``num_experts`` counts the experts held here, those from
  ``ep_rank * num_experts`` on of a router ``num_experts * ep_size`` wide;
  what the absent experts would add is left out, and the vocabulary is the
  slice the tree holds;
- a position that is not masked is never unmasked: the step takes ``min(n_s,
  masks left)`` where the published top-k over ``-inf`` entries could name a
  given position (and would write its own token's draw over it);
- ties between confidences go to the lower index (``torch.topk`` leaves them
  open);
- every matrix is held ``[in, out]``; the weights are the program's seeded
  random ones.

``model["_precision"]`` (``"highest"`` unless given) and ``model["_without"]``
(a set of names: ``"qk_norm"``) are for the tests and controls that hold the
program to each part. Parameters come from the system under test a layer at
a time, each matrix cast to float32 where it is used; attention and logits
are taken in blocks of rows, and every layer is waited for, so that the
device's peak stays the program's own.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
ROW_BLOCK = 2048  # rows of logits at a time, each moved to the host
QUERY_BLOCK = 256  # query rows of attention at a time


def _rms_norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w.astype(F32)


def _rotate(x, cos, sin):
    """``x`` [S, heads, D] by angles [S, D / 2], halves paired."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


@partial(jax.jit, static_argnames=("nh", "nkv", "eps", "bd", "qk_norm",
                                   "precision"))
def attention(lp, x, cos, sin, *, nh: int, nkv: int, eps: float, bd: int,
              qk_norm: bool, precision: str):
    """The attention half on the normed stream ``x`` [S, H] of one sequence
    from position 0, block-causal."""
    S = x.shape[0]
    with jax.default_matmul_precision(precision):
        q = (x @ lp["wq"].astype(F32)).reshape(S, nh, -1)
        k = (x @ lp["wk"].astype(F32)).reshape(S, nkv, -1)
        v = (x @ lp["wv"].astype(F32)).reshape(S, nkv, -1)
        if qk_norm:
            q = _rms_norm(q, lp["q_norm"], eps)
            k = _rms_norm(k, lp["k_norm"], eps)
        q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
        D, g = q.shape[-1], nh // nkv
        k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
        block_of = jnp.arange(S) // bd
        out = []
        for r in range(0, S, QUERY_BLOCK):
            scores = jnp.einsum("shd,thd->hst", q[r:r + QUERY_BLOCK], k) \
                / math.sqrt(D)
            seen = block_of[None, :] <= block_of[r:r + QUERY_BLOCK, None]
            p = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1)
            out.append(jnp.einsum("hst,thd->shd", p, v))
        return jnp.concatenate(out).reshape(S, nh * D) @ lp["wo"].astype(F32)


@partial(jax.jit, static_argnames=("k", "precision"))
def route(x, router, *, k: int, precision: str):
    """(experts [S, k], weights [S, k]): the ``k`` largest of ``softmax(x
    W_r)``, ties to the lower index, over their sum."""
    with jax.default_matmul_precision(precision):
        scores = jax.nn.softmax(x @ router.astype(F32), axis=-1)
    order = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(scores, order, axis=-1)
    return order, w / jnp.sum(w, axis=-1, keepdims=True)


@partial(jax.jit, static_argnames=("precision",))
def _swiglu(x, w_gate, w_up, w_down, *, precision: str):
    with jax.default_matmul_precision(precision):
        return (jax.nn.silu(x @ w_gate.astype(F32))
                * (x @ w_up.astype(F32))) @ w_down.astype(F32)


def experts(lp, x, model: dict, precision: str):
    """The routed experts held here: [S, H]. No shared expert."""
    chosen, weights = route(x, lp["router"],
                            k=int(model["num_experts_per_tok"]),
                            precision=precision)
    held = int(model["num_experts"])
    first = int(model.get("ep_rank", 0)) * held
    y = jnp.zeros_like(x)
    for e in range(held):
        w = jnp.sum(jnp.where(chosen == first + e, weights, 0.0), axis=-1)
        y = y + w[:, None] * _swiglu(x, lp["w1"][e], lp["w3"][e],
                                     lp["w2"][e], precision=precision)
    return y


def layer(lp, h, cos, sin, model: dict):
    """One layer on one sequence, ``h`` [S, H] float32."""
    eps = float(model["rms_norm_eps"])
    precision = model.get("_precision", "highest")
    h = h + attention(
        lp, _rms_norm(h, lp["attn_norm"], eps), cos, sin,
        nh=int(model["num_attention_heads"]),
        nkv=int(model["num_key_value_heads"]), eps=eps,
        bd=int(model["block_length"]),
        qk_norm="qk_norm" not in model.get("_without", ()),
        precision=precision)
    return h + experts(lp, _rms_norm(h, lp["mlp_norm"], eps), model,
                       precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def head(final_norm, lm_head, h, *, eps: float, precision: str):
    with jax.default_matmul_precision(precision):
        return _rms_norm(h, final_norm, eps) @ lm_head.astype(F32)


def forward_logits(params, tokens, model: dict, device=None):
    """Logits [B, S, V] (numpy float32, V the slice of the vocabulary the
    tree holds) at every position of ``tokens`` [B, S] (masked positions
    hold ``mask_token_id``), each sequence from position 0 under the
    block-causal rule."""
    device = device or jax.devices()[0]
    tokens = np.asarray(tokens)
    S = tokens.shape[1]
    D = int(model.get("head_dim") or int(model["hidden_size"])
            // int(model["num_attention_heads"]))
    inv = float(model["rope_theta"]) ** (-np.arange(0, D, 2) / D)
    ang = np.arange(S)[:, None] * inv[None, :]
    cos, sin = (jax.device_put(t.astype(np.float32), device)
                for t in (np.cos(ang), np.sin(ang)))
    hs = [jax.device_put(params["embed"][jnp.asarray(t)], device).astype(F32)
          for t in tokens]
    for i in range(int(model["num_hidden_layers"])):
        lp = jax.device_put(jax.tree.map(lambda v: v[i], params["layers"]),
                            device)
        hs = [layer(lp, h, cos, sin, model) for h in hs]
        del lp
        jax.block_until_ready(hs)  # one layer's copy resident at a time
    fn = jax.device_put(params["final_norm"], device)
    lm = jax.device_put(params["lm_head"], device)
    return np.stack([np.concatenate([
        np.asarray(head(fn, lm, h[r:r + ROW_BLOCK],
                        eps=float(model["rms_norm_eps"]),
                        precision=model.get("_precision", "highest")))
        for r in range(0, S, ROW_BLOCK)]) for h in hs])


def loss(params, tokens, targets, model: dict, device=None) -> float:
    """Mean cross-entropy of ``targets`` [B, S] under ``forward_logits`` of
    ``tokens``, unshifted (``logits[i]`` scores position ``i``), over the
    sliced vocabulary. No cell reads it (``common.load_reference`` asks for
    one)."""
    logits = jnp.asarray(forward_logits(params, tokens, model, device))
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.asarray(np.asarray(targets))[..., None], axis=-1)
    return float(-jnp.mean(picked))


def transfer_counts(bd: int, steps: int) -> list:
    """Positions each of ``steps`` denoise steps owes a block of ``bd``."""
    return [bd // steps + (s < bd % steps) for s in range(steps)]


def unmask(logits, x0, masked, owed: int, model: dict):
    """Which masked positions of a block take their draw at this step:
    [Bd] bool, from ``logits`` [Bd, V], the draws ``x0`` and the flags."""
    z = logits.astype(np.float64)
    z = z - z.max(axis=-1, keepdims=True)
    p = np.exp(z) / np.exp(z).sum(axis=-1, keepdims=True)
    conf = np.where(masked, p[np.arange(len(x0)), x0], -np.inf)
    best = np.argsort(-conf, kind="stable")[:owed]
    take = np.zeros_like(masked)
    take[best] = True
    take &= masked  # min(owed, masks left)
    if model["remasking"] == "low_confidence_dynamic":
        high = masked & (conf > float(model["confidence_threshold"]))
        if high.sum() >= owed:
            take = high
    elif model["remasking"] != "low_confidence_static":
        raise ValueError(model["remasking"])
    return take


def generate(params, prompt, n_new: int, model: dict, device=None,
             eos_id=None, trace=None) -> list:
    """The ``n_new`` tokens (fewer: the stream ended at ``eos_id``) the
    published loop generates behind ``prompt`` at temperature 0, no cache:
    every forward is ``forward_logits`` of the sequence so far and the
    current block. ``trace``, a list, is given every forward's (sequence
    fed, masked flags of its last block, logits of that block)."""
    bd, steps = int(model["block_length"]), int(model["denoising_steps"])
    mask_id = int(model["mask_token_id"])
    owed = transfer_counts(bd, steps)
    seq, out = list(prompt), []
    done = len(seq) // bd * bd  # the prompt's whole blocks
    while len(out) < n_new:
        given = seq[done:]
        block = np.array(given + [mask_id] * (bd - len(given)))
        masked = np.arange(bd) >= len(given)
        for s in range(steps):
            if not masked.any():
                break
            fed = seq[:done] + block.tolist()
            logits = forward_logits(params, [fed], model, device)[0, done:]
            if trace is not None:
                trace.append((fed, masked.copy(), logits))
            x0 = np.argmax(logits, axis=-1)
            take = unmask(logits, x0, masked, owed[s], model)
            block = np.where(take, x0, block)
            masked &= ~take
        new = block[len(given):].tolist()[: n_new - len(out)]
        if eos_id is not None and eos_id in new:
            return out + new[: new.index(eos_id) + 1]
        out += new
        seq = seq[:done] + block.tolist()
        done += bd
    return out
