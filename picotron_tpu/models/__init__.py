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
  layers, ``granite_hybrid``, finds its own row from it); in a decode
  block its cache dict also holds ``"active"`` [B] (parked and in budget),
  which a block that keeps K/V alone need not read;
- ``UNSLICED``: names of a group's leaves the scan hands its layers
  whole, with the layer's row in them under ``"row"`` (``()``: none);
- ``serving_rope_tables(m, seq_len, dtype)``: the angle tables of the cache window;
- ``cache_pspecs(m, quantized, dp=...)`` and ``init_cache(m, slots,
  max_seq_len, dtype=..., quantized=..., tp=...)``: the contiguous cache
  (K/V heads for the Llama block, as many to a row as fill its lanes on a
  'tp' axis that wide; latent rows for ``deepseek_v32``);
- ``CARRIES_STATE`` (absent: false): the cache holds a state with no token
  axis, which cannot be fed a token twice; the engine then holds the
  window to whole prefill chunks;
- ``STAT_NAMES``: the counters a layer returns, an int32 vector under
  ``"stats"`` in its dict (``()``: none, and the programs have no such
  output).
"""

from picotron_tpu.models import llama  # noqa: F401

# the key under which a layer that counts returns its counters
STATS = "stats"


def model_module(m):
    """The module that builds ``m.model_type``'s block (a ``ModelConfig``).
    Imported on demand: ``deepseek_v32`` needs the inference package, which
    needs this one."""
    if m.model_type == "deepseek_v32":
        from picotron_tpu.models import deepseek_v32

        return deepseek_v32
    if m.model_type == "granitemoehybrid":
        from picotron_tpu.models import granite_hybrid

        return granite_hybrid
    if m.model_type == "llama":
        return llama
    raise ValueError(f"unknown model_type {m.model_type!r}")
