"""Segmented multi-LoRA matmul: one dispatch, many adapters.

The weight-side half of multi-tenant serving (inference/tenancy.py):
every tenant's low-rank adapter pair lives stacked in one
``[T, in, r]`` / ``[T, r, out]`` pack, and a per-row adapter-id vector
``ids [B]`` selects which pair each batch row runs — so ONE
decode/verify/prefill dispatch mixes tenants (S-LoRA / Punica's
segmented-gather matmul, adapted to our leaf-form dispatch seam). The
base matmul — dense bf16 or the PR 13 fused int8 dequant — is untouched:
the adapter contributes an ADDITIVE fp32 residual

    residual[b] = (x[b] @ a[ids[b]]) @ b[ids[b]]

added onto the base output at the ``models/llama.py::matmul`` seam.

Slot 0 of every pack is the reserved NULL adapter (A = B = 0), so
base-only rows ride the same dispatch and their residual is exactly
zero — adding it never changes a base value beyond the sign of a zero,
which no comparison downstream observes. An engine with no adapter pack
configured never builds adapter leaves at all, so default serving traces
byte-identical programs to the pre-tenancy build.

Two implementations behind one entry point, ``lora_matmul(x, a, b,
ids)``:

- **Pallas kernel** (TPU, or ``interpret=True`` for the CPU parity
  suite): a ``(B,)`` grid with ``ids`` as a scalar-prefetch operand
  (``pltpu.PrefetchScalarGridSpec``) — the BlockSpec index maps read
  ``ids_ref[b]`` so each grid instance's A/B blocks are DMA'd straight
  from the chosen adapter's pack rows; no gathered copy of the adapter
  ever materializes in HBM. Per instance: two tiny MXU contractions
  ([S, K] @ [K, r] then [S, r] @ [r, out]) with fp32 accumulation.
- **XLA fallback** (off-TPU serving / any platform): ``a[ids]`` /
  ``b[ids]`` gathers plus two batched einsums with the same fp32
  accumulation — identical math, XLA's gather instead of prefetched
  index maps.

The rank axis r is tiny (8-64) next to the lane quantum; the kernel
trades a sliver of lane utilization for zero gather traffic, which is
the right trade at decode batch sizes. Shapes with huge S (long prefill
chunks) stay bounded because S rides inside one grid instance's block —
the chunked prefill's C is already the VMEM-sized unit.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from picotron_tpu.utils import on_tpu

# Adapter packs store fp32: the residual accumulates in fp32 end to end,
# and adapter bytes are negligible next to the base weights they modify.
ADAPTER_DTYPE = jnp.float32

# The reserved null adapter every pack carries in slot 0 (A = B = 0):
# base-only rows point here and their residual is exactly zero.
NULL_ADAPTER = 0


def is_lora_weight(leaf) -> bool:
    """Whether a parameter leaf is an adapter-wrapped weight — the dict
    form ``{"w": base_leaf, "a": [T, in, r], "b": [T, r, out],
    "ids": [B]}`` the model's matmul sites dispatch on
    (models/llama.py::matmul). ``w`` may itself be the quantized
    ``{"q", "s"}`` pair — the base dispatch recurses."""
    return isinstance(leaf, dict) and set(leaf) == {"w", "a", "b", "ids"}


# --------------------------------------------------------------------------- #
# the kernel
# --------------------------------------------------------------------------- #


def _lora_kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    """One batch row's adapter residual. The grid instance's A/B blocks
    were already steered to ``ids[b]``'s pack rows by the scalar-prefetch
    index maps — the kernel body never sees the id, only its adapter.
    Both contractions accumulate in fp32 (rank is tiny; precision is
    free)."""
    del ids_ref  # consumed by the BlockSpec index maps, not the body
    xb = x_ref[0].astype(jnp.float32)  # [S, K]
    t = lax.dot_general(xb, a_ref[0], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)  # [S, r]
    o_ref[0] = lax.dot_general(t, b_ref[0], (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def lora_matmul_pallas(x, a, b, ids, *, interpret: bool = False):
    """The Pallas path: x [B, S, K], a [T, K, r], b [T, r, N], ids [B]
    int32 -> [B, S, N] fp32. Grid is one instance per batch row; ``ids``
    rides as the scalar-prefetch operand so each instance's a/b
    BlockSpecs index straight into its adapter's pack rows."""
    B, S, K = x.shape
    T, _, r = a.shape
    N = b.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, S, K), lambda bi, ids_ref: (bi, 0, 0)),
            pl.BlockSpec((1, K, r), lambda bi, ids_ref: (ids_ref[bi], 0, 0)),
            pl.BlockSpec((1, r, N), lambda bi, ids_ref: (ids_ref[bi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, S, N), lambda bi, ids_ref: (bi, 0, 0)),
    )
    return pl.pallas_call(
        _lora_kernel,
        name="lora_matmul",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, N), jnp.float32),
        interpret=interpret,
    )(ids, x, a, b)


def lora_matmul_xla(x, a, b, ids):
    """The XLA fallback (off-TPU serving and any non-Pallas platform):
    gather each row's adapter pair, then the same two fp32-accumulated
    contractions as the kernel — batched einsums instead of a grid."""
    ag = a[ids]  # [B, K, r]
    bg = b[ids]  # [B, r, N]
    t = jnp.einsum("bsk,bkr->bsr", x.astype(jnp.float32), ag,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bsr,brn->bsn", t, bg,
                      preferred_element_type=jnp.float32)


def lora_matmul(x, a, b, ids, *, impl: str | None = None,
                interpret: bool = False):
    """Per-row adapter residual ``(x[b] @ a[ids[b]]) @ b[ids[b]]``.

    x: [B, S, in] activations (any float dtype); a: [T, in, r] fp32
    stacked adapter down-projections; b: [T, r, out] fp32 stacked
    up-projections; ids: [B] int32 adapter slots (0 = the null adapter —
    exact zero residual). Returns [B, S, out] fp32.

    ``impl``: "pallas" | "xla" | None (auto: the Pallas kernel on TPU,
    the XLA gather-einsum elsewhere — quant_matmul's dispatch rule).
    ``interpret`` forces the Pallas interpreter (the CPU parity suite).
    """
    if x.ndim != 3:
        raise ValueError(f"lora_matmul expects x [B, S, in]; got {x.shape}")
    if a.ndim != 3 or b.ndim != 3 or a.shape[2] != b.shape[1] \
            or a.shape[0] != b.shape[0]:
        raise ValueError(
            f"adapter pack shapes disagree: a {a.shape} (want [T, in, r]) "
            f"vs b {b.shape} (want [T, r, out])")
    if impl is None:
        impl = "pallas" if (on_tpu() or interpret) else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown lora_matmul impl {impl!r} (pallas|xla)")
    ids = jnp.asarray(ids, jnp.int32)
    if impl == "pallas":
        return lora_matmul_pallas(x, a, b, ids, interpret=interpret)
    return lora_matmul_xla(x, a, b, ids)
