"""What a block says of a configuration it is handed: the refusals every
block but the Llama block shares, worded once, and the shapes of check on a
block's own keys that several blocks make.

A block module declares ``WHY``, its reason for each thing it cannot do yet
(``"training"`` and the keys of ``_asked``; a key it does not hold is not
refused), and ``validate(cfg, for_training)``: ``refuse`` with that table,
then the checks on its own keys, through the helpers here where three blocks
or more make the same check and inline where it is the block's alone.
``Config.validate`` calls it (``models.model_module``), so a configuration
file, a training entry point and an engine keyword are all refused in the
same words."""

from __future__ import annotations


def who(m) -> str:
    """How a refusal names the block of ``m`` (a ``ModelConfig``)."""
    return f"model_type {m.model_type!r}"


def _asked(cfg) -> tuple:
    """((key of ``WHY``, whether ``cfg`` asks for it, what a refusal calls
    it), ...) in the order refused, behind training."""
    d, inf = cfg.distributed, cfg.inference
    return (
        ("tp", d.tp_size > 1, f"tp_size > 1 (got {d.tp_size})"),
        ("dp", inf.dp_size > 1, f"inference.dp_size > 1 (got {inf.dp_size})"),
        ("paged", inf.kv_layout == "paged", "inference.kv_layout 'paged' "
         "(nor the prefix reuse that rests on it)"),
        ("kv_int8", inf.kv_cache_dtype == "int8",
         "inference.kv_cache_dtype 'int8'"),
        ("weight_int8", inf.weight_dtype == "int8",
         "inference.weight_dtype 'int8'"),
        ("lora", bool(inf.tenancy.tenants or inf.tenancy.manifest),
         "LoRA adapters (inference.tenancy)"),
        ("speculation", inf.spec_len > 0,
         f"speculation (inference.spec_len {inf.spec_len})"),
        ("flash", inf.attend_impl == "flash",
         "inference.attend_impl 'flash'"),
        ("overlap", inf.overlap, "inference.overlap"),
        ("mixed_dispatch", inf.mixed_dispatch, "inference.mixed_dispatch"),
        ("key_schedule", inf.key_schedule == "slot",
         "inference.key_schedule 'slot'"),
    )


def refuse(cfg, for_training: bool, why: dict) -> None:
    """Raise for the first thing ``cfg`` asks for that ``why`` has a reason
    against, by the block's name."""
    block = who(cfg.model)
    if for_training and "training" in why:
        raise ValueError(
            f"{block} is served, not trained: training is not implemented "
            f"for this block ({why['training']}; train_step builds the "
            "Llama block only)")
    for key, asked, what in _asked(cfg):
        if asked and key in why:
            raise ValueError(f"{block} does not support {what}: {why[key]}")


# the reasons that are the same whatever the block
LLAMA_ONLY = {
    "weight_int8": "its matmuls take dense weights only",
    "lora": "the adapter pack is shaped for the Llama block's seven "
            "projections",
    "overlap": "the lookahead dispatch is not implemented for this block",
    "mixed_dispatch": "the fused prefill lane embeds and heads through the "
                      "Llama block",
    "key_schedule": "it serves through the round-keyed programs only",
}

# a block that keeps a recurrent state beside K/V (``granite_hybrid``,
# ``nemotron_h``, ``solar_open2``)
RECURRENT_STATE = {
    **LLAMA_ONLY,
    "training": "no backward through the chunked scan and the expert share",
    "tp": "the recurrent state has no tp sharding and the block holds no tp "
          "collectives; its share of a layer is ep_size/ep_rank",
    "dp": "the recurrent state has no slot axis over 'dp'",
    "paged": "a recurrent state has no token axis to page and no snapshot to "
             "resume a shared prefix from; set kv_layout: 'contiguous'",
    "kv_int8": "the state is float32 and K/V are stored in the model's dtype",
    "speculation": "a rejected draft cannot be rolled back out of a "
                   "recurrent state by rewinding a length",
    "flash": "the recurrent state has no kernel, and forcing one for the "
             "attention layers' prefill chunks is untested ('auto' runs it "
             "for the decode step)",
    "overlap": "the lookahead dispatch is not implemented for a block that "
               "carries a state",
}

# a block that keeps rings beside full-length K/V (``afmoe``, ``mimo_v2``)
RINGS = {
    **LLAMA_ONLY,
    "training": "no backward through the expert share",
    "tp": "the block holds no tp collectives and its rings are not sharded; "
          "its share of a layer is ep_size/ep_rank",
    "dp": "the rings have no slot axis over 'dp'",
    "paged": "one pool and one block table cannot yet tell the layers that "
             "keep a sequence's history from those that keep a window; set "
             "kv_layout: 'contiguous'",
    "kv_int8": "both kinds of K/V are stored in the model's dtype",
    "speculation": "a rejected draft's rows have already overwritten the "
                   "ring's oldest, and rewinding a length does not bring "
                   "them back",
    "flash": "the sliced flash-decode kernel reads a prefix, not a ring "
             "('auto' runs the stacked kernel for the decode step)",
}


# --------------------------------------------------------------------------- #
# the shapes of check on a block's own keys
# --------------------------------------------------------------------------- #


def positive(m, *names: str) -> None:
    for name in names:
        if getattr(m, name) < 1:
            raise ValueError(f"{who(m)} needs model.{name} >= 1")


def pinned(m, **only) -> None:
    """The published keys the block implements one value of."""
    for name, want in only.items():
        if getattr(m, name) != want:
            raise ValueError(
                f"{who(m)} implements model.{name} = {want!r} only (got "
                f"{getattr(m, name)!r})")


def check(m, *rows: tuple) -> None:
    """``(what is wrong, how to say it)`` rows: the first that holds."""
    for bad, why in rows:
        if bad:
            raise ValueError(f"{who(m)}: {why}")


def ep_share(m, held: str = "") -> None:
    """The chip's share of the routed experts: ``ep_rank`` among ``ep_size``,
    and ``num_experts_per_tok`` inside the router's width, the experts held
    (``model.<held>``) x ``ep_size`` (no ``held``: the block checks its
    router itself)."""
    width = getattr(m, held) * m.ep_size if held else None
    check(m,
          (not 0 <= m.ep_rank < m.ep_size,
           f"ep_rank {m.ep_rank} outside [0, ep_size {m.ep_size})"),
          (held and m.num_experts_per_tok > width,
           f"num_experts_per_tok {m.num_experts_per_tok} passes the router's "
           f"width {width} ({held} x ep_size)"))


def layer_kinds(m, field: str, kinds, need=None, note: str = "") -> None:
    """``model.<field>`` names one of ``kinds`` for each of
    ``num_hidden_layers``, and both of ``need`` (``kinds``, where they are
    two): the kinds whose layers keep a cache leaf of their own."""
    got, n = getattr(m, field), m.num_hidden_layers
    if not got or len(got) != n or any(t not in kinds for t in got):
        raise ValueError(
            f"{who(m)} needs model.{field}: one of "
            f"{' | '.join(repr(k) for k in kinds)} for each of the {n} "
            f"layers (got {got!r}{note})")
    a, b = need or kinds
    if a not in got or b not in got:
        raise ValueError(
            f"{who(m)} needs at least one {a!r} and one {b!r} layer in "
            f"model.{field}: the cache holds a leaf of each kind")


def held_layers(m, count: int, lead: int = 0, what: str = "layers",
                behind: str = "") -> None:
    """Where the ``count`` layers held lie in the published model: from
    ``first_layer`` on, behind the ``lead`` layers that are held whatever
    the cut, inside ``total_layers`` (0: not said)."""
    first, total = m.first_layer, m.total_layers
    check(m, (first < 0 or total and (first < lead or first + count > total),
              f"{what} first_layer {first} .. + {count} lie outside "
              f"total_layers {total}{behind}"))
