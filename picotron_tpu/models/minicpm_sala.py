"""The MiniCPM-SALA block (``model_type: "minicpm_sala"``) as pure functions
over a parameter pytree: lightning linear-attention layers and block-sparse
NoPE attention layers (InfLLM-v2) in the order ``mixer_types`` gives, a
SwiGLU behind each. Serving path only (``Config.validate`` refuses the rest
by name).

The equations (``x`` the normed stream; RMSNorm, eps ``rms_norm_eps``; no
bias anywhere):

- stream: ``h = scale_emb * E[tokens]``; a layer: ``h += r * mixer(norm(h))``,
  then ``h += r * SwiGLU(norm(h))`` with ``r = scale_depth /
  sqrt(total_layers)`` (the whole model's depth, not the layers held here);
  out: ``logits = norm(h) W_head / (hidden_size / dim_model_base)``, the head
  untied;
- ``lightning-attn`` layer (``lightning_nh`` heads of ``lightning_head_dim``,
  a key/value head each): ``q, k, v = x W_q, x W_k, x W_v``; RMSNorm over each
  head's width of ``q`` and of ``k`` (``qk_norm``); RoPE on both at the token's
  position; per head the state ``S`` [d, d] in float32: ``S_t = exp(-slope)
  S_{t-1} + v_t (x) k_t``, ``o_t = S_t q_t / sqrt(d)``; RMSNorm over all heads
  of ``o`` together (``use_output_norm``); ``o *= sigmoid(x W_g)``
  (``use_output_gate``); ``W_o``. ``slope`` [heads] is a float32 leaf of the
  layer (``decay_slopes``: Lightning Attention's, by the layer's index in the
  whole model), so a checkpoint's values take its place with no code change;
- ``minicpm4`` layer (GQA, no position embedding, no q/k norm), with
  ``sparse_config``'s sizes ``st = kernel_stride``, ``2 st = kernel_size``,
  ``bs = block_size``: compressed keys ``kc_c = mean(k[st c : st c + 2 st])``
  per kv head, visible to the query at ``t`` when ``st c + 2 st - 1 <= t``.
  Stage 1: per query head ``p = softmax_c(q . kc_c / sqrt(d))`` over the
  visible ``c``, summed over the query heads of a kv head; the score of block
  ``b`` (tokens ``bs b ..``) is the largest of that sum over the windows that
  touch it; block 0 (``init_blocks``) and the ``window_size / bs`` blocks up
  to the query's own are forced (+inf); the kv head keeps the ``topk`` blocks
  of highest score among those ``<= t // bs``, ties to the lower index.
  Stage 2: each query head's softmax over the tokens ``s <= t`` of its kv
  head's kept blocks, scores ``q . k / sqrt(d)``. A query at ``t < dense_len``
  attends over every ``s <= t``: the rule is the query's position, so a
  token's output does not depend on what follows it. ``o *= sigmoid(x W_g)``
  (``attn_use_output_gate``); ``W_o``.

The cache (``init_cache``), each leaf over the layers of its own kind: ``k``,
``v`` [sparse layers, slots, kv heads, T, d], a kv head's keys one after the
other, so that a block of ``bs`` keys is one contiguous piece and the leaf
lies as the contractions read it (tokens-major, the compiler re-laid the
whole leaf inside every program: PERF.md, PR 34); ``kc`` [sparse layers,
slots, kv heads, T / st, d], row ``r`` holding window ``c = r - 1`` (row 0 is
never visible), written when the window's last key arrives: by a prefill chunk for
every window that ends inside it (the first began in the chunk before: its
first ``st`` keys are read back from ``k``), by a decode step when ``(t + 1) %
st == 0``, from the last ``2 st`` keys in the cache; ``state`` [lightning
layers, slots, heads, d, d] float32. The state has no token axis: a row that
is not ``live`` leaves it exactly as it was (``dt = 0`` in ``ops/ssm.py``),
and a prompt's first chunk (``pos == 0``) starts from zeros.

A decode step gathers the kept blocks of K and V and attends over those
(``topk * bs`` rows a slot, layer and kv head, whatever the context); a
prefill chunk keeps a masked running softmax over the live key blocks.

Every layer function returns, beside the updated cache leaves, what it
counted (``STATS``, in the order of ``STAT_NAMES``; docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from picotron_tpu.config import Config, ModelConfig
from picotron_tpu.inference import kv_cache
from picotron_tpu.models import (STATS, carry_state, leaf_row, live_rows,
                                 llama, runs, served_whole, state_counts,
                                 support)
from picotron_tpu.models.experts import swiglu
from picotron_tpu.models.llama import param_bytes  # noqa: F401 - the seam
from picotron_tpu.ops.attention import NEG_INF
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.rope import apply_rope, precompute_rope
from picotron_tpu.ops.select import select_keys
from picotron_tpu.ops.ssm import ssm_scan, ssm_step

# what a layer counts, in the order of the vector (under ``STATS``): key
# blocks kept and key blocks up to the query's own (a kv head, layer and live
# query row under the sparse rule), live query rows under the sparse and
# under the dense rule (a sparse layer), live slot-layers a decode step
# advanced, lightning layers decode steps ran, live tokens through a prefill
# scan (a layer)
STAT_NAMES = ("sparse_blocks_selected", "sparse_blocks_visible",
              "sparse_rows", "dense_rows", "lightning_state_updates",
              "lightning_layer_steps", "lightning_scan_tokens")

UNSLICED = ()
# the state has no token axis and cannot be fed a token twice: the engine
# holds the window to whole prefill chunks
CARRIES_STATE = True
F32 = jnp.float32

KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}
# keys attended, and queries scored against the compressed keys, at a time:
# bounds the [S, heads, keys] products of a prefill chunk
KEY_BLOCK = 1024
QUERY_BLOCK = 128
# rows of a lightning layer's chunked scan at a time (``ops/ssm.py``)
SCAN_CHUNK = 256
LEAVES = ("k", "v", "kc", "state")  # the cache's, beside "lengths"

# what the block cannot do yet, and why (``support.refuse``)
WHY = {
    **support.RECURRENT_STATE,
    "training": "no backward through the block selection and the chunked "
                "scan",
    "tp": "the lightning state and the compressed keys have no tp sharding "
          "and the block holds no tp collectives",
    "dp": "the state and the compressed keys have no slot axis over 'dp'",
    "paged": "the block selection gathers key blocks of a contiguous leaf, "
             "and neither the compressed keys nor the lightning state are "
             "paged; set kv_layout: 'contiguous'",
    "kv_int8": "the state is float32 and K, V and the compressed keys are "
               "stored in the model's dtype",
    "flash": "the flash-decode kernel reads every live key, not the chosen "
             "blocks",
}


def validate(cfg: Config, for_training: bool) -> None:
    """What the block cannot do yet and what it needs of its keys, each
    refused by name (``Config.validate`` calls it)."""
    m = cfg.model
    support.refuse(cfg, for_training, WHY)
    support.positive(m, "lightning_nh", "lightning_nkv", "lightning_head_dim",
                     "dim_model_base")
    support.layer_kinds(m, "mixer_types", tuple(KINDS))
    support.check(
        m,
        (m.lightning_nkv != m.lightning_nh,
         f"lightning_nkv {m.lightning_nkv} must equal lightning_nh "
         f"{m.lightning_nh} (a state a head)"),
        (m.lightning_head_dim % 2,
         f"lightning_head_dim {m.lightning_head_dim} must be even (RoPE "
         "rotates halves)"))
    support.held_layers(m, m.num_hidden_layers)
    support.pinned(m, lightning_scale="1/sqrt(d)", lightning_use_rope=True,
                   attn_use_rope=False, attn_use_output_gate=True,
                   qk_norm=True, use_output_norm=True, use_output_gate=True,
                   tie_word_embeddings=False, rope_scaling=None)
    sc = m.sparse_config or {}
    need = ("kernel_size", "kernel_stride", "block_size", "init_blocks",
            "window_size", "topk", "dense_len")
    if any(int(sc.get(k, 0)) < 1 for k in need):
        raise ValueError(
            f"{support.who(m)} needs model.sparse_config with "
            f"{', '.join(need)} each >= 1 (got {m.sparse_config!r})")
    ks, st, bs = sc["kernel_size"], sc["kernel_stride"], sc["block_size"]
    chunk = cfg.inference.prefill_chunk
    support.check(
        m,
        (ks != 2 * st or bs % st or sc["window_size"] % bs
         or sc["dense_len"] % bs,
         f"sparse_config needs kernel_size {ks} = 2 x kernel_stride {st}, "
         f"and block_size {bs}, window_size {sc['window_size']} and "
         f"dense_len {sc['dense_len']} in whole strides and blocks"),
        (sc["init_blocks"] + sc["window_size"] // bs > sc["topk"],
         f"sparse_config's forced blocks (init_blocks {sc['init_blocks']} + "
         f"window_size / block_size {sc['window_size'] // bs}) pass topk "
         f"{sc['topk']}"),
        (chunk % st,
         f"inference.prefill_chunk ({chunk}) must be a multiple of "
         f"sparse_config.kernel_stride ({st}): a chunk writes whole rows of "
         "compressed keys"))


# --------------------------------------------------------------------------- #
# shapes, groups, parameters
# --------------------------------------------------------------------------- #


def total_layers(m: ModelConfig) -> int:
    return m.total_layers or m.num_hidden_layers


def residual_scale(m: ModelConfig) -> float:
    return m.scale_depth / math.sqrt(total_layers(m))


def decay_slopes(m: ModelConfig, layer: int) -> np.ndarray:
    """[heads] float32: Lightning Attention's slopes for layer ``layer`` of
    the whole model, ``2^(-8 (h + 1) / heads) * (1 - layer / (depth - 1) +
    1e-5)``; the state decays by ``exp(-slope)`` a token."""
    nh = m.lightning_nh
    base = 2.0 ** (-8.0 * np.arange(1, nh + 1) / nh)
    return (base * (1.0 - layer / max(total_layers(m) - 1, 1) + 1e-5)
            ).astype(np.float32)


def kinds(m: ModelConfig) -> list:
    """``mixer_types`` by the names the tree's groups carry."""
    return [KINDS[t] for t in m.mixer_types]


def layer_groups(m: ModelConfig) -> list:
    """[(name of the stacked group in the tree, its layer function, how many
    layers)]: one group a run of ``mixer_types``, scanned in turn. Each
    function knows where its run begins, among all layers and among those
    of its kind."""
    fns = {"sparse": sparse_layer, "lightning": lightning_layer}
    return [(f"{kind}_{i}", partial(fns[kind], first=first, kind_first=kf), n)
            for i, (kind, first, kf, n) in enumerate(runs(kinds(m)))]


def kind_counts(m: ModelConfig) -> dict:
    return {k: kinds(m).count(k) for k in ("sparse", "lightning")}


def _shapes(m: ModelConfig, kind: str) -> dict:
    """Matmul leaves of a layer, (in, out) like every weight here."""
    H, I = m.hidden_size, m.intermediate_size
    if kind == "lightning":
        D = Dk = m.lightning_nh * m.lightning_head_dim
    else:
        D = m.num_attention_heads * m.head_dim
        Dk = m.num_key_value_heads * m.head_dim
    return {"wq": (H, D), "wk": (H, Dk), "wv": (H, Dk), "wg": (H, D),
            "wo": (D, H), "w_gate": (H, I), "w_up": (H, I),
            "w_down": (I, H)}


# Seeded weights are drawn so that each mechanism of the block is loud
# enough in the logits for a comparison to see a fault in it (as
# ``deepseek_v32.INIT_GAIN``; PERF.md, PR 34, has the readings). The sparse
# layers' ``wo`` 48 times wider: a flat softmax over four thousand keys is a
# mean, a sixtieth of the stream, and at 16 neither which blocks were kept
# nor the dense rule past ``dense_len`` moved a logit by the check's limit
# (2.0 % against 0.96 sound). At 64 every such fault read 14 % and more, but
# the sound program's own reading, which bfloat16's choice of another block
# than float32's feeds, reached 2.0 % of the 3 allowed on one seed of
# sixteen: 48 leaves it room. The lightning layers need none: their output
# norm makes the state's read-out as loud as the stream whatever its size.
INIT_GAIN = {"sparse": {"wo": 48.0}, "lightning": {}}
# the embedding's draw: N(0, 1) / scale_emb, a unit-rms entry into the
# stream (``scale_emb`` times an N(0, 1) row would bury every layer's part)


def init_params(key, m: ModelConfig, pp_size: int = 1,
                interleave: int = 1) -> dict:
    """Global parameter pytree from ``key``: linear weights U(+-gain *
    sqrt(1 / fan_in)) drawn in the model's dtype, norm weights ones, the
    lightning layers' ``slope`` as ``decay_slopes`` gives it (float32)."""
    if pp_size != 1 or interleave != 1:
        raise ValueError("minicpm_sala is served on one stage (pp_size 1)")
    dt = jnp.dtype(m.dtype)
    H, V = m.hidden_size, m.vocab_size

    def uniform(k, shape, fan_in, gain=1.0):
        bound = gain * math.sqrt(1.0 / fan_in)
        return jax.random.uniform(k, shape, dt, -bound, bound)

    def group(gkey, n: int, kind: str, first: int) -> dict:
        ones = lambda w: jnp.ones((n, w), dt)
        out = {"mixer_norm": ones(H), "mlp_norm": ones(H)}
        for i, (name, shape) in enumerate(sorted(_shapes(m, kind).items())):
            out[name] = uniform(jax.random.fold_in(gkey, i), (n,) + shape,
                                shape[0], INIT_GAIN[kind].get(name, 1.0))
        if kind == "lightning":
            out["q_norm"] = ones(m.lightning_head_dim)
            out["k_norm"] = ones(m.lightning_head_dim)
            out["out_norm"] = ones(m.lightning_nh * m.lightning_head_dim)
            out["slope"] = jnp.asarray(np.stack(
                [decay_slopes(m, m.first_layer + first + j)
                 for j in range(n)]))
        return out

    params = {
        "embed": (jax.random.normal(jax.random.fold_in(key, 0), (V, H), F32)
                  / m.scale_emb).astype(dt),
        "final_norm": jnp.ones((H,), dt),
        "lm_head": uniform(jax.random.fold_in(key, 1), (H, V), H),
    }
    for i, (kind, first, _, n) in enumerate(runs(kinds(m))):
        params[f"{kind}_{i}"] = group(jax.random.fold_in(key, 2 + i), n,
                                      kind, first)
    return params


param_pspecs, num_params, cache_pspecs = served_whole(
    "minicpm_sala", init_params, LEAVES)


# --------------------------------------------------------------------------- #
# into and out of the stream; serving state
# --------------------------------------------------------------------------- #


def embed_lookup(w, tokens, cfg: Config):
    """``scale_emb * E[tokens]``, in the embedding's dtype."""
    return llama.embed_lookup(w, tokens) * jnp.asarray(
        cfg.model.scale_emb, w.dtype)


def head_logits(params, h, cfg: Config):
    """Final norm, the untied head, over ``hidden_size / dim_model_base``."""
    m = cfg.model
    logits = llama.head_logits(params, h, cfg)
    return logits / jnp.asarray(m.hidden_size / m.dim_model_base,
                                logits.dtype)


def serving_rope_tables(m: ModelConfig, seq_len: int, dtype) -> tuple:
    """(cos, sin) [seq_len, lightning_head_dim]: the lightning layers
    rotate; the sparse layers read neither."""
    return precompute_rope(seq_len, m.lightning_head_dim, m.rope_theta,
                           dtype)


def init_cache(m: ModelConfig, slots: int, max_seq_len: int, dtype=None,
               quantized: bool = False, tp: int = 1) -> dict:
    """Zeroed cache for ``slots`` sequences, four kinds of leaf, each over
    the layers of its own kind: ``k``/``v`` [sparse layers, slots, kv heads,
    T, head_dim]; ``kc`` [sparse layers, slots, kv heads, T / kernel_stride,
    head_dim], the compressed keys (row ``r``: window ``r - 1``); ``state``
    [lightning layers, slots, heads, d, d] float32."""
    assert not quantized and tp == 1
    dt = jnp.dtype(dtype if dtype is not None else m.dtype)
    n = kind_counts(m)
    st = m.sparse_config["kernel_stride"]
    if max_seq_len % m.sparse_config["block_size"]:
        raise ValueError(
            f"minicpm_sala: max_seq_len ({max_seq_len}) must be a multiple "
            f"of sparse_config.block_size ({m.sparse_config['block_size']})")
    kvh, hd = m.num_key_value_heads, m.head_dim
    kv = (n["sparse"], slots, kvh, max_seq_len, hd)
    return {
        "k": jnp.zeros(kv, dt), "v": jnp.zeros(kv, dt),
        "kc": jnp.zeros((n["sparse"], slots, kvh, max_seq_len // st, hd),
                        dt),
        "state": jnp.zeros((n["lightning"], slots, m.lightning_nh,
                            m.lightning_head_dim, m.lightning_head_dim),
                           F32),
        "lengths": jnp.zeros((slots,), jnp.int32),
    }


# --------------------------------------------------------------------------- #
# what both kinds of layer share
# --------------------------------------------------------------------------- #


def _leaves(cache: dict) -> dict:
    return {n: v for n, v in cache.items() if n not in ("live", "active")}


def _finish(lp, h, m: ModelConfig, out: dict, stats: tuple):
    """The SwiGLU half, and the layer's counters beside its cache leaves."""
    x = rms_norm(h, lp["mlp_norm"], m.rms_norm_eps)
    h = h + jnp.asarray(residual_scale(m), h.dtype) * swiglu(
        x, lp["w_gate"], lp["w_up"], lp["w_down"])
    out[STATS] = jnp.stack(stats)
    return h, out


# --------------------------------------------------------------------------- #
# the lightning linear-attention layer
# --------------------------------------------------------------------------- #


def lightning_mixer(lp, x, cos, sin, state_in, live, m: ModelConfig,
                    one_step: tuple) -> tuple:
    """The mixer on the normed stream ``x`` [B, S, H] from the state
    ``state_in`` [B, heads, d, d]: (output [B, S, H], the state behind the
    last ``live`` row). The recurrence is ``ops/ssm.py``'s with ``dt = 1``
    on live rows and 0 elsewhere, ``A = -slope``, ``x = v``, ``B = k``, ``C =
    q``, each head its own. ``one_step`` is empty, or on a decode step
    ``(row,)``: ``state_in`` is then the whole stacked leaf and so is the
    state returned, that row of it advanced where it lies
    (``ops/ssm.py::ssm_step``)."""
    B, S, _ = x.shape
    nh, hd = m.lightning_nh, m.lightning_head_dim
    eps = m.rms_norm_eps
    q = rms_norm((x @ lp["wq"]).reshape(B, S, nh, hd), lp["q_norm"], eps)
    k = rms_norm((x @ lp["wk"]).reshape(B, S, nh, hd), lp["k_norm"], eps)
    v = (x @ lp["wv"]).reshape(B, S, nh, hd)
    q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    dt = jnp.broadcast_to(live[..., None].astype(F32), (B, S, nh))
    A = -lp["slope"].astype(F32)
    if one_step:
        with jax.named_scope("sala/lightning_step"):
            y, state = ssm_step(v, dt, A, k, q, state_in, *one_step)
    else:
        with jax.named_scope("sala/lightning_scan"):
            y, state = ssm_scan(v, dt, A, k, q, state_in, SCAN_CHUNK)
    o = (y * hd ** -0.5).reshape(B, S, nh * hd)
    o = rms_norm(o, lp["out_norm"], eps).astype(x.dtype)
    o = o * jax.nn.sigmoid(x @ lp["wg"])
    return o @ lp["wo"], state


def lightning_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                    return_kv: bool = False, layer=None, live=None, *,
                    first: int = 0, kind_first: int = 0):
    """A lightning layer, then the SwiGLU. ``llama.decoder_layer``'s
    contract; the returned dict also holds ``STATS``. Three shapes of call:
    no cache (a whole sequence from zeros: the state behind its last live
    row is returned as a one-slot block), a ``slot`` entry (a prefill chunk
    carries that slot's state on, from zeros where ``pos`` is 0), neither (a
    decode step advances every live slot)."""
    m = cfg.model
    live = live_rows(cache, live, h)
    x = rms_norm(h, lp["mixer_norm"], m.rms_norm_eps)
    y, new, decode = carry_state(
        cache, cache, ("state",),
        (((m.lightning_nh, m.lightning_head_dim, m.lightning_head_dim),
          F32),),
        None if cache is None else leaf_row(layer, first, kind_first), pos,
        h, lambda state_in, step: lightning_mixer(
            lp, x, cos, sin, state_in, live, m, one_step=step))
    h = h + jnp.asarray(residual_scale(m), h.dtype) * y
    if cache is None:
        out = new if return_kv else {}
    else:
        out = {**_leaves(cache), **new}
    zero = jnp.zeros((), jnp.int32)
    return _finish(lp, h, m, out, (zero,) * 4 + state_counts(live, decode))


# --------------------------------------------------------------------------- #
# the block-sparse attention layer
# --------------------------------------------------------------------------- #


def compress_block(k, prev, st: int):
    """Compressed keys of the windows that end inside a block of keys ``k``
    [B, kv heads, S, d] (``S`` in whole strides) whose last ``st``
    predecessors are ``prev`` [B, kv heads, st, d]: [B, kv heads, S / st, d]
    float32, entry ``j`` the mean of the ``2 st`` keys that end with the
    block's key ``st (j + 1) - 1``."""
    B, nkv, S, hd = k.shape
    ext = jnp.concatenate([prev.astype(k.dtype), k], axis=2).astype(F32)
    groups = jnp.sum(ext.reshape(B, nkv, S // st + 1, st, hd), axis=3)
    return (groups[:, :, :-1] + groups[:, :, 1:]) / (2 * st)


def _write_rows(src: dict, name: str, vals, pos, row) -> jnp.ndarray:
    """Leaf ``name`` [layers, slots, kv heads, T, d] with ``vals`` [B, kv
    heads, S, d] written at layer ``row`` in place: a one-slot block's rows
    from ``pos[0]`` on (a ``slot`` entry), or a decode step's one row a slot
    at its own ``pos`` [B]."""
    leaf = src[name]
    vals = vals.astype(leaf.dtype)
    if "slot" in src:
        # held to the layout the leaf is resident in: left free, a chunk's
        # contractions pull the whole V leaf tokens-minor on entry and push
        # it back on exit (as ``granite_hybrid.mamba_layer`` holds its state)
        zero = jnp.zeros((), jnp.int32)
        return kv_cache.row_major(lax.dynamic_update_slice(
            kv_cache.row_major(leaf), vals[None],
            (row, jnp.asarray(src["slot"], jnp.int32), zero, pos[0], zero)))
    B, nkv, S, _ = vals.shape
    assert S == 1, "a batched block of several rows is the verify shape"
    return leaf.at[row, jnp.arange(B)[:, None], jnp.arange(nkv)[None, :],
                   pos[:, None]].set(vals[:, :, 0])


def _write_compressed(src: dict, k, pos, row, live, st: int) -> jnp.ndarray:
    """The ``kc`` leaf with the windows written that the keys ``k`` [B, kv
    heads, S, d] (already in ``src["k"]``) complete: a one-slot block's from
    its own rows and the ``st`` keys before them, a decode step's from the
    last ``2 st`` keys in the cache, where a slot's new key ends a
    window."""
    kc = src["kc"]
    B, nkv, S, hd = k.shape
    zero = jnp.zeros((), jnp.int32)
    if "slot" in src:
        slot = jnp.asarray(src["slot"], jnp.int32)
        prev = lax.dynamic_slice(
            src["k"], (row, slot, zero, jnp.maximum(pos[0] - st, 0), zero),
            (1, 1, nkv, st, hd))[0]
        new = compress_block(k, prev, st).astype(kc.dtype)
        return lax.dynamic_update_slice(
            kc, new[None], (row, slot, zero, pos[0] // st, zero))
    ends = live[:, 0] & ((pos + 1) % st == 0)
    rows = jnp.clip(pos[:, None] + 1 - 2 * st
                    + jnp.arange(2 * st, dtype=jnp.int32)[None, :], 0,
                    src["k"].shape[3] - 1)
    slots, heads = jnp.arange(B)[:, None], jnp.arange(nkv)[None, :]
    last = src["k"][row, slots[:, :, None], heads[:, :, None],
                    rows[:, None, :]]  # [B, kv heads, 2 st, d]
    new = jnp.mean(last.astype(F32), axis=2).astype(kc.dtype)
    # a slot whose key ends no window writes past the leaf: dropped
    r = jnp.where(ends, (pos + 1) // st - 1, kc.shape[3])
    return kc.at[row, slots, heads, r[:, None]].set(new)


def block_scores(q, kc, pos_q, m: ModelConfig):
    """Stage 1: [B, S, kv heads, blocks] float32, each key block's score for
    the queries ``q`` [B, S, heads, d] at ``pos_q`` [B, S] from the
    compressed keys ``kc`` [B, kv heads, rows, d] (row ``r``: window ``r -
    1``): the softmax over the visible windows, summed over a kv head's
    query heads, the largest of a block's windows; +inf for the forced
    blocks, -inf past the query's own block."""
    sc = m.sparse_config
    st, bs = sc["kernel_stride"], sc["block_size"]
    B, S, nq, hd = q.shape
    nkv = m.num_key_value_heads
    R = kc.shape[2]
    per = bs // st
    s = jnp.einsum("bsgqd,bgrd->bsgqr", q.reshape(B, S, nkv, nq // nkv, hd),
                   kc, preferred_element_type=F32) * hd ** -0.5
    r = jnp.arange(R, dtype=jnp.int32)
    # window r - 1 ends at key st r + st - 1
    vis = ((r[None, None, :] >= 1)
           & (st * r[None, None, :] + st - 1 <= pos_q[..., None]))
    vis = vis[:, :, None, None, :]
    top = jnp.max(jnp.where(vis, s, NEG_INF), axis=-1, keepdims=True)
    p = jnp.where(vis, jnp.exp(s - top), 0.0)
    total = jnp.sum(p, axis=-1, keepdims=True)
    p = jnp.sum(p / jnp.where(total > 0, total, 1.0), axis=3)  # [B,S,g,R]
    # block b is touched by windows 4b - 1 .. 4b + 3: rows 4b .. 4b + 4
    p = p.reshape(B, S, nkv, R // per, per)
    nxt = jnp.concatenate([p[..., 1:, 0], jnp.zeros_like(p[..., :1, 0])],
                          axis=-1)
    score = jnp.maximum(jnp.max(p, axis=-1), nxt)
    blk = jnp.arange(R // per, dtype=jnp.int32)[None, None, None, :]
    cur = (pos_q // bs)[:, :, None, None]
    forced = (blk < sc["init_blocks"]) \
        | (blk > cur - sc["window_size"] // bs)
    score = jnp.where(forced, jnp.inf, score)
    return jnp.where(blk <= cur, score, -jnp.inf)


def select_blocks(q, kc, pos_q, m: ModelConfig):
    """[B, S, kv heads, blocks] bool: the key blocks each query attends
    over: the ``topk`` of highest ``block_scores``, or, for a query before
    ``dense_len``, every block up to its own."""
    sc = m.sparse_config
    B, S = pos_q.shape
    Sb = S if S <= QUERY_BLOCK else math.gcd(S, QUERY_BLOCK)

    def one(q_b, pos_b):
        return select_keys(block_scores(q_b, kc, pos_b, m), sc["topk"])

    if Sb == S:
        chosen = one(q, pos_q)
    else:
        def blocks(a):  # [B, S, ...] -> [S / Sb, B, Sb, ...]
            return jnp.moveaxis(a.reshape(B, S // Sb, Sb, *a.shape[2:]), 1, 0)

        chosen = lax.map(lambda xs: one(*xs), (blocks(q), blocks(pos_q)))
        chosen = jnp.moveaxis(chosen, 0, 1).reshape(B, S, *chosen.shape[3:])
    blk = jnp.arange(chosen.shape[-1], dtype=jnp.int32)
    upto = blk[None, None, None, :] <= (pos_q // sc["block_size"])[
        :, :, None, None]
    dense = (pos_q < sc["dense_len"])[:, :, None, None]
    return jnp.where(dense, upto, chosen)


def _key_rows(src: dict, name: str, row, t0, n: int):
    """Keys ``t0 .. t0 + n`` of leaf ``name`` at layer ``row``, read where
    they lie: [B, kv heads, n, d] ([1, ...] of a ``slot`` entry's slot)."""
    leaf = src[name]
    B = 1 if "slot" in src else leaf.shape[1]
    zero = jnp.zeros((), jnp.int32)
    at = (jnp.asarray(row, jnp.int32),
          jnp.asarray(src.get("slot", 0), jnp.int32), zero,
          jnp.asarray(t0, jnp.int32), zero)
    return lax.dynamic_slice(
        leaf, at, (1, B, leaf.shape[2], n, leaf.shape[4]))[0]


def attend_masked(q, chosen, src: dict, row, pos_q, m: ModelConfig):
    """Stage 2 for a block of queries: softmax attention of ``q`` [B, S,
    heads, d] over the keys ``s <= pos_q`` of the blocks ``chosen`` [B, S,
    kv heads, blocks], [B, S, heads, d] float32. The window's live blocks of
    ``KEY_BLOCK`` keys are read once each, where they lie, the softmax kept
    running over them (max, sum, weighted values)."""
    B, S, nq, hd = q.shape
    nkv = m.num_key_value_heads
    bs = m.sparse_config["block_size"]
    T = src["k"].shape[3]
    Tb = T if T <= KEY_BLOCK else math.gcd(T, KEY_BLOCK)
    qg = q.reshape(B, S, nkv, nq // nkv, hd)
    scale = hd ** -0.5

    def body(j, carry):
        mx, l, acc = carry
        kb = _key_rows(src, "k", row, j * Tb, Tb)
        vb = _key_rows(src, "v", row, j * Tb, Tb)
        s = jnp.einsum("bsgqd,bgtd->bsgqt", qg, kb,
                       preferred_element_type=F32) * scale
        on = lax.dynamic_slice_in_dim(chosen, j * (Tb // bs), Tb // bs, 3)
        on = jnp.repeat(on, bs, axis=3)  # [B, S, kvh, Tb]
        t = j * Tb + jnp.arange(Tb, dtype=jnp.int32)
        on = (on & (t[None, None, None, :] <= pos_q[:, :, None, None])
              )[:, :, :, None, :]
        m_new = jnp.maximum(mx, jnp.max(jnp.where(on, s, NEG_INF), axis=-1))
        p = jnp.where(on, jnp.exp(s - m_new[..., None]), 0.0)
        fade = jnp.exp(mx - m_new)
        l = l * fade + jnp.sum(p, axis=-1)
        acc = acc * fade[..., None] + jnp.einsum(
            "bsgqt,bgtd->bsgqd", p.astype(vb.dtype), vb,
            preferred_element_type=F32)
        return m_new, l, acc

    shape = (B, S, nkv, nq // nkv)
    carry = (jnp.full(shape, NEG_INF, F32), jnp.zeros(shape, F32),
             jnp.zeros(shape + (hd,), F32))
    if T == Tb:
        _, l, acc = body(0, carry)
    else:
        live_blocks = jnp.minimum(jnp.max(pos_q), T - 1) // Tb + 1
        _, l, acc = lax.fori_loop(0, live_blocks, body, carry)
    return (acc / l[..., None]).reshape(B, S, nq, hd)


def gather_blocks(leaf, row, idx, bs: int):
    """Blocks ``idx`` [B, kv heads, n] of ``bs`` rows each of a K or V leaf
    [layers, slots, kv heads, T, d] at layer ``row``, each kv head's own:
    [B, kv heads, n, bs, d]. A block's rows are contiguous in the row-major
    leaf, so this is a gather over a block axis."""
    L, slots, nkv, T, hd = leaf.shape
    B = idx.shape[0]
    blocks = leaf.reshape(L, slots, nkv, T // bs, bs, hd)
    return blocks[row, jnp.arange(B)[:, None, None],
                  jnp.arange(nkv)[None, :, None], idx]


def attend_gathered(q, chosen, src: dict, row, pos, m: ModelConfig):
    """Stage 2 for a decode step: ``q`` [B, 1, heads, d] at ``pos`` [B] over
    the ``topk`` blocks ``chosen`` [B, 1, kv heads, blocks] keeps, gathered
    out of K and V (``topk * block_size`` rows a slot and kv head, whatever
    the context): [B, 1, heads, d] float32."""
    sc = m.sparse_config
    bs, K = sc["block_size"], sc["topk"]
    B, _, nq, hd = q.shape
    nkv = m.num_key_value_heads
    on = chosen[:, 0]  # [B, kvh, NB]
    NB = on.shape[-1]
    K = min(K, NB)
    # the kept blocks' indices in rising order: the j-th set bit of a row
    rank = jnp.cumsum(on, axis=-1, dtype=jnp.int32) - 1
    blk = jnp.arange(NB, dtype=jnp.int32)
    hit = on[:, :, None, :] & (rank[:, :, None, :]
                               == jnp.arange(K, dtype=jnp.int32)[:, None])
    idx = jnp.sum(jnp.where(hit, blk, 0), axis=-1)  # [B, kvh, K]
    kept = jnp.arange(K, dtype=jnp.int32) < jnp.sum(
        on, axis=-1, dtype=jnp.int32)[..., None]
    kg = gather_blocks(src["k"], row, idx, bs)
    vg = gather_blocks(src["v"], row, idx, bs)
    qg = q[:, 0].reshape(B, nkv, nq // nkv, hd)
    s = jnp.einsum("bgqd,bgkrd->bgqkr", qg, kg,
                   preferred_element_type=F32) * hd ** -0.5
    tok = idx[..., None] * bs + jnp.arange(bs, dtype=jnp.int32)
    ok = (kept[..., None] & (tok <= pos[:, None, None, None]))[:, :, None]
    s = jnp.where(ok, s, NEG_INF)
    top = jnp.max(s, axis=(-2, -1), keepdims=True)
    p = jnp.where(ok, jnp.exp(s - top), 0.0)
    l = jnp.sum(p, axis=(-2, -1))
    o = jnp.einsum("bgqkr,bgkrd->bgqd", p.astype(vg.dtype), vg,
                   preferred_element_type=F32)
    return (o / l[..., None]).reshape(B, 1, nq, hd)


def attend_prefix(q, src: dict, row, pos, m: ModelConfig):
    """A decode step under the dense rule: ``q`` [B, 1, heads, d] over every
    key ``s <= pos`` among the first ``dense_len`` rows: [B, 1, heads, d]
    float32."""
    B, _, nq, hd = q.shape
    nkv = m.num_key_value_heads
    n = min(src["k"].shape[3], m.sparse_config["dense_len"])
    kb, vb = (_key_rows(src, name, row, 0, n) for name in ("k", "v"))
    qg = q[:, 0].reshape(B, nkv, nq // nkv, hd)
    s = jnp.einsum("bgqd,bgtd->bgqt", qg, kb,
                   preferred_element_type=F32) * hd ** -0.5
    ok = (jnp.arange(n, dtype=jnp.int32)[None, :] <= pos[:, None])[
        :, None, None, :]
    p = jax.nn.softmax(jnp.where(ok, s, NEG_INF), axis=-1)
    o = jnp.einsum("bgqt,bgtd->bgqd", p.astype(vb.dtype), vb,
                   preferred_element_type=F32)
    return o.reshape(B, 1, nq, hd)


def sparse_attention(q, src: dict, row, pos_q, live, m: ModelConfig,
                     gather: bool) -> tuple:
    """Both stages for the queries ``q`` [B, S, heads, d] at ``pos_q`` over
    the leaves of ``src`` at layer ``row``: (output [B, S, heads, d]
    float32, the four sparse counters)."""
    sc = m.sparse_config
    with jax.named_scope("sala/select_blocks"):
        kc = _key_rows(src, "kc", row, 0, src["kc"].shape[3])
        chosen = select_blocks(q, kc, pos_q, m)
    dense = pos_q < sc["dense_len"]
    with jax.named_scope("sala/attend_blocks"):
        if gather:
            pos = pos_q[:, 0]
            o = attend_gathered(q, chosen, src, row, pos, m)
            # a slot still before dense_len attends over its whole prefix;
            # the branch is not run while no live slot is that short
            o = lax.cond(
                jnp.any(dense & live),
                lambda: jnp.where(dense[:, :, None, None],
                                  attend_prefix(q, src, row, pos, m), o),
                lambda: o)
        else:
            o = attend_masked(q, chosen, src, row, pos_q, m)
    sparse = live & ~dense
    n_sparse = jnp.sum(sparse, dtype=jnp.int32)
    selected = jnp.sum(jnp.where(sparse[:, :, None, None], chosen, False),
                       dtype=jnp.int32)
    visible = jnp.sum(jnp.where(sparse, pos_q // sc["block_size"] + 1, 0),
                      dtype=jnp.int32) * m.num_key_value_heads
    return o, (selected, visible, n_sparse,
               jnp.sum(live & dense, dtype=jnp.int32))


def sparse_layer(lp, h, cos, sin, cfg: Config, cache=None, pos=None,
                 return_kv: bool = False, layer=None, live=None, *,
                 first: int = 0, kind_first: int = 0):
    """A block-sparse attention layer (GQA, no rotation: ``cos``/``sin`` are
    not read), then the SwiGLU. ``llama.decoder_layer``'s contract; the
    returned dict also holds ``STATS``. K and V are written at this layer's
    row of their leaves, and the compressed keys the new keys complete
    beside them, before any is scored."""
    m = cfg.model
    B, S, _ = h.shape
    hd = m.head_dim
    st = m.sparse_config["kernel_stride"]
    live = live_rows(cache, live, h)
    if pos is None:
        pos = jnp.zeros((B,), jnp.int32)
    pos_q = pos[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    x = rms_norm(h, lp["mixer_norm"], m.rms_norm_eps)
    q = (x @ lp["wq"]).reshape(B, S, m.num_attention_heads, hd)
    k = (x @ lp["wk"]).reshape(B, S, m.num_key_value_heads, hd)
    v = (x @ lp["wv"]).reshape(B, S, m.num_key_value_heads, hd)
    # a kv head's keys one after the other, as the leaves hold them
    kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    if cache is None:
        # a whole sequence at once: its own rows are the keys
        def grown(a, n):  # [B, kv heads, rows, d] out to ``n`` more rows
            return jnp.pad(a, ((0, 0), (0, 0), (0, n), (0, 0)))

        with jax.named_scope("sala/compress_keys"):
            kp = grown(kt, -S % st)
            kc = compress_block(kp, jnp.zeros_like(kp[:, :, :st]),
                                st).astype(k.dtype)
        # keys and compressed rows out to whole blocks
        grow = -S % m.sparse_config["block_size"]
        src = {"k": grown(kt, grow)[None], "v": grown(vt, grow)[None],
               "kc": grown(kc, (S + grow) // st - kc.shape[2])[None]}
        row = 0
        out = {"k": kt, "v": vt, "kc": kc} if return_kv else {}
    else:
        row = leaf_row(layer, first, kind_first)
        src = _leaves(cache)
        src["k"] = _write_rows(src, "k", kt, pos, row)
        src["v"] = _write_rows(src, "v", vt, pos, row)
        with jax.named_scope("sala/compress_keys"):
            src["kc"] = _write_compressed(src, kt, pos, row, live, st)
        out = src
    a, counted = sparse_attention(
        q, src, row, pos_q, live, m,
        gather=cache is not None and "slot" not in cache and S == 1)
    a = a.reshape(B, S, -1).astype(x.dtype) * jax.nn.sigmoid(x @ lp["wg"])
    h = h + jnp.asarray(residual_scale(m), h.dtype) * (a @ lp["wo"])
    zero = jnp.zeros((), jnp.int32)
    return _finish(lp, h, m, dict(out), counted + (zero,) * 3)
