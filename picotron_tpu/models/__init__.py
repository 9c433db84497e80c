"""The model blocks, and the seam the serving stack reaches them through.

``model_module(m)`` is the module of ``m.model_type``. The engine,
``tools/serve.py`` and the weight loaders take from it, by the same names
whatever the block:

- ``init_params(key, m)``, ``param_pspecs(m, weight_dtype=...)``,
  ``param_bytes(params)``: the tree, its shardings, its size;
- ``embed_lookup(embed, tokens, cfg=...)``, ``head_logits(params, h,
  cfg)``: into and out of the residual stream, both ends the block's own
  (its multipliers; a block whose head is its embedding reads
  ``params["embed"]`` in both);
- ``layer_groups(m)``: ``[(name of a stacked group in the tree, its layer
  function, how many layers)]``, scanned one after the other over one cache;
  every layer function keeps ``llama.decoder_layer``'s contract and is
  handed its global index (a block whose cache leaves run over different
  layers finds its own row from it, ``leaf_row``); in a decode block its
  cache dict also holds ``"active"`` [B] (parked and in budget), which a
  block that keeps K/V alone need not read;
- ``UNSLICED``: names of a group's leaves the scan hands its layers
  whole, with the layer's row in them under ``"row"`` (``()``: none);
- ``serving_rope_tables(m, seq_len, dtype)``: the angle tables of the cache window;
- ``cache_pspecs(m, quantized, dp=...)`` and ``init_cache(m, slots,
  max_seq_len, dtype=..., quantized=..., tp=...)``: the contiguous cache,
  whose leaves beside ``"lengths"`` a block other than Llama names in
  ``LEAVES`` (what each holds is its ``init_cache``'s to say);
- ``RING_CACHE`` (absent: false): some leaves are rings a prefill chunk's
  writes must fit; ``init_cache`` then also takes ``prefill_chunk``;
- ``CARRIES_STATE`` (absent: false): the cache holds a state with no token
  axis, which cannot be fed a token twice; the engine then holds the
  window to whole prefill chunks;
- ``GENERATES`` (absent: ``"tokens"``): how the engine generates with the
  block. ``"tokens"``: a token a slot and step, fed the token before it
  (``engine._decode_block_impl``). ``"blocks"``: by diffusion over blocks of
  ``model.block_length`` positions; a round denoises and commits whole
  blocks (``engine._blocks_impl``) and counts its own forwards
  (``engine.stat_names``), and admission prefills a prompt's whole blocks;
- ``STAT_NAMES``: the counters a layer returns, an int32 vector under
  ``"stats"`` in its dict (``()``: none, and the programs have no such
  output);
- ``WHY`` and ``validate(cfg, for_training)`` (absent, as in the Llama
  block: nothing is refused): the block's reason against each thing it
  cannot do yet, and its checks of a configuration, the shared refusals
  and its own keys (``models/support.py``). ``Config.validate`` calls it.

A block served whole on one chip takes ``param_pspecs``, ``num_params`` and
``cache_pspecs`` from ``served_whole`` below.
"""

import importlib
import math

from picotron_tpu.models import llama  # noqa: F401

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

# the key under which a layer that counts returns its counters
STATS = "stats"


# What the blocks whose layers alternate between kinds of mixer
# (``granite_hybrid``, ``minicpm_sala``, ``afmoe``, ``mimo_v2``,
# ``solar_open2``) share: the
# runs of the per-layer pattern, a layer's row of its own kind's cache leaves, the rows that count
# (``nemotron_h``, whose runs are all one layer long, stacks by units of its
# own and takes the last two).


def runs(kinds) -> list:
    """[(kind, first layer, layers of that kind before it, count)] of the
    runs of equal entries in ``kinds``, one entry a layer."""
    out, seen = [], {}
    for i, kind in enumerate(kinds):
        if out and out[-1][0] == kind:
            out[-1][3] += 1
        else:
            out.append([kind, i, seen.get(kind, 0), 1])
        seen[kind] = seen.get(kind, 0) + 1
    return [tuple(r) for r in out]


def leaf_row(layer, first: int, kind_first: int):
    """A layer's row of its own kind's cache leaves, from the scan's global
    index and where its run begins (``runs``)."""
    return jnp.asarray(layer, jnp.int32) - first + kind_first


def live_rows(cache, live, h):
    """[B, S] bool: the rows that are counted, routed and advance a state:
    the engine's ``live`` (real tokens of parked slots), less the slots a
    decode block's ``active`` entry leaves out (parked, out of budget)."""
    cache = cache or {}
    if live is None:
        live = cache.get("live")
    if live is None:
        live = jnp.ones(h.shape[:2], bool)
    if "active" in cache:
        live = live & cache["active"][:, None]
    return live


# What the blocks that keep a recurrent state beside K/V (``granite_hybrid``,
# ``nemotron_h``, ``minicpm_sala``, ``solar_open2``, ``falcon_h1``) share: the way from the
# state leaves to a mixer and back, and the three counters of such a layer.


def carry_state(cache, leaves, names, like, row, pos, h, mixer) -> tuple:
    """Run a recurrent ``mixer`` from a layer's part of its state leaves and
    put back what it leaves behind: ``mixer(*ins, one_step) -> (y, *outs)``,
    an in and an out for each of ``names``, leaves of ``leaves`` shaped
    [layers of the kind, slots, ...] of which ``row`` is this layer's (the
    last is the state proper; before it, what a conv keeps of its last
    inputs). Three shapes of call, by ``cache``: None (a whole sequence from
    zeros, ``like`` = a slot's (shape, dtype) for each name: what is behind
    the last live row comes back as a one-slot block); a ``slot`` entry (a
    prefill chunk carries that slot's part on, from zeros where ``pos`` is
    0: admission, whatever the slot's last occupant left; a ``gate`` entry
    chooses between what the chunk leaves and what was there); neither (a
    decode step advances every slot, and where ``h`` is one row a slot the
    mixer is handed the state leaf whole and ``one_step = (row,)`` and
    returns the leaf, that row of it advanced). Returns ``(y, {name: the
    leaf written, or the block}, decode)``."""
    # on demand: the inference package needs this one
    from picotron_tpu.inference import kv_cache

    if cache is None:
        y, *news = mixer(*(jnp.zeros((h.shape[0],) + shape, dt)
                           for shape, dt in like), ())
        return y, dict(zip(names, news)), False
    decode = "slot" not in cache
    step = (row,) if decode and h.shape[1] == 1 else ()
    # a decode step's elementwise pass takes the leaves as they lie. A
    # chunk's contractions must be held to that: left free they pull the
    # whole state leaf into their own order on entry and push it back on
    # exit (two copies of 2.4 GB a chunk in Granite's cell; as
    # ``kv_cache.cache_write`` holds the K/V leaf it writes)
    pin = (lambda x: x) if decode else kv_cache.row_major
    zero = jnp.zeros((), jnp.int32)
    slot = None if decode else jnp.asarray(cache["slot"], jnp.int32)
    ins = []
    for name in names:
        if step and name == names[-1]:
            ins.append(leaves[name])
            continue
        x = lax.dynamic_index_in_dim(pin(leaves[name]), row, 0, False)
        if not decode:
            x = lax.dynamic_slice_in_dim(x, slot, 1, axis=0)
            x = jnp.where(pos[0] == 0, jnp.zeros_like(x), x)
        ins.append(x)
    y, *news = mixer(*ins, step)
    out = {}
    for name, new, old in zip(names, news, ins):
        new = new.astype(leaves[name].dtype)
        if step and name == names[-1]:  # the leaf itself, its row advanced
            out[name] = new
        elif decode:
            out[name] = lax.dynamic_update_index_in_dim(
                leaves[name], new, row, 0)
        else:
            if cache.get("gate") is not None:
                new = jnp.where(cache["gate"], new, old)
            at = (row, slot) + (zero,) * (new.ndim - 1)
            out[name] = pin(lax.dynamic_update_slice(
                leaves[name], pin(new)[None], at))
    return y, out, decode


def state_counts(live, decode: bool) -> tuple:
    """A recurrent layer's three counters for one call: (slots a decode step
    advanced, decode steps of the layer, tokens a prefill scanned)."""
    n_live = jnp.sum(live, dtype=jnp.int32)
    zero = jnp.zeros((), jnp.int32)
    return (n_live, zero + 1, zero) if decode else (zero, zero, n_live)


def served_whole(block: str, init_params, leaves: tuple) -> tuple:
    """``(param_pspecs, num_params, cache_pspecs)`` of a block served whole
    on one chip, from its ``init_params`` and the names of its cache's
    leaves: every leaf replicated (tp_size 1: the block's share of a layer
    is ``ep_size``/``ep_rank``, a cut and not a mesh axis), the tree read
    off the shapes ``init_params`` draws. ``block`` names it in the
    refusal of anything else (``Config.validate`` refuses it first)."""

    def shapes(m):
        return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), m))

    def param_pspecs(m, fsdp: bool = False, weight_dtype: str = "bf16"):
        if fsdp or weight_dtype != "bf16":
            raise ValueError(f"{block} serves dense weights, unsharded")
        return jax.tree.map(lambda _: P(), shapes(m))

    def num_params(m) -> int:
        return sum(math.prod(s.shape) for s in jax.tree.leaves(shapes(m)))

    def cache_pspecs(m, quantized: bool = False, dp: int = 1) -> dict:
        assert not quantized and dp == 1
        return {n: P() for n in leaves + ("lengths",)}

    return param_pspecs, num_params, cache_pspecs


# ``model_type`` -> the module of ``picotron_tpu.models`` that builds it
BLOCKS = {
    "llama": "llama",
    "deepseek_v32": "deepseek_v32",
    "granitemoehybrid": "granite_hybrid",
    "minicpm_sala": "minicpm_sala",
    "afmoe": "afmoe",
    "mimo_v2": "mimo_v2",
    "KeyeVL2": "keye_vl2",
    "nemotron_h": "nemotron_h",
    "solar_open2": "solar_open2",
    "sdar_moe": "sdar_moe",
    "falcon_h1": "falcon_h1",
}


def model_module(m):
    """The module that builds ``m.model_type``'s block (a ``ModelConfig``).
    Imported on demand: ``deepseek_v32`` needs the inference package, which
    needs this one."""
    if m.model_type not in BLOCKS:
        raise ValueError(
            f"unknown model_type {m.model_type!r} ({'|'.join(BLOCKS)})")
    return importlib.import_module(
        "picotron_tpu.models." + BLOCKS[m.model_type])
