"""Compile every main-path Pallas kernel for a described v5e at SmolLM-1.7B
widths (H 2048, 32/32 heads of 64, FFN 8192, vocab 49152, seq 2048, bf16).

No chip is needed and nothing runs: the TPU compiler installed with jax
compiles for a ``v5e:2x2`` that is described, not attached, and raises what
the chip's compiler would raise (a block shape the lowering refuses, a slice
Mosaic cannot align, too much VMEM). Interpret-mode parity lives in
tests/test_pallas_kernels.py and tests/test_decode_kernel.py; the compiled
kernels run against their oracles on the chip in ``chip_smoke.py``.

The topology is described inside a module-scoped fixture — never at import,
in a ``skipif`` or in a ``parametrize`` argument — so every xdist worker
collects the same tests and only the worker given this file loads the TPU
library. Keep these compiles in this ONE file.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from picotron_tpu.ops.pallas import quant_matmul as qm
from picotron_tpu.ops.pallas.decode_attention import flash_decode_attention
from picotron_tpu.ops.pallas.flash_attention import (
    flash_attention,
    flash_attention_with_lse,
    flash_block_grads,
)
from picotron_tpu.ops.pallas.rmsnorm import rms_norm_pallas

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
HID, HEADS, D, FFN, VOCAB, SEQ = 2048, 32, 64, 8192, 49152, 2048
SCALE = D ** -0.5
# the serving geometry chip_smoke.py drives: 8 slots x 2048, 64-row pages
SLOTS, PAGE = 8, 64
MAXP = SEQ // PAGE
NPAGES = SLOTS * MAXP + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on one described chip, with the persistent compile cache
    off around these compiles: an entry written for a described device
    cannot be read back without one and would warn on every later run."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sum32(x):
    return x.astype(F32).sum()


def _flash_fwd():
    q = ((4, SEQ, HEADS, D), BF16)
    return (lambda q, k, v: flash_attention(q, k, v, SCALE)), [q, q, q]


def _flash_bwd():
    q = ((4, SEQ, HEADS, D), BF16)
    return jax.grad(lambda q, k, v: _sum32(flash_attention(q, k, v, SCALE)),
                    argnums=(0, 1, 2)), [q, q, q]


def _flash_block_grads():
    # the ring-attention building block: one off-diagonal (full-attend)
    # block's gradients from a global out/lse, at cp=2's half sequence
    q = ((1, SEQ // 2, HEADS, D), BF16)
    lse = ((1, SEQ // 2, HEADS), F32)
    return (lambda q, k, v, o, l, do: flash_block_grads(
        q, k, v, o, l, do, SCALE, causal=False)), [q, q, q, q, lse, q]


def _flash_with_lse():
    q = ((1, SEQ // 2, HEADS, D), BF16)
    return (lambda q, k, v: flash_attention_with_lse(
        q, k, v, SCALE, causal=False)), [q, q, q]


def _rms_fwd():
    return rms_norm_pallas, [((4 * SEQ, HID), BF16), ((HID,), BF16)]


def _rms_bwd():
    return jax.grad(lambda x, w: _sum32(rms_norm_pallas(x, w)),
                    argnums=(0, 1)), [((4 * SEQ, HID), BF16), ((HID,), BF16)]


def _decode(layout, b, s):
    """flash_decode_attention in one cache layout the engine can select,
    at one of its three call shapes (decode, verify window, prefill
    chunk)."""
    q = ((b, s, HEADS, D), BF16)
    lens = ((b,), I32)
    cache = lambda dt: ((b, SEQ, HEADS, D), dt)
    scales = ((b, SEQ, HEADS), F32)
    pool = lambda dt: ((NPAGES, PAGE, HEADS, D), dt)
    pscales = ((NPAGES, PAGE, HEADS), F32)
    tables = ((b, MAXP), I32)
    if layout == "contiguous":
        return (lambda q, k, v, n: flash_decode_attention(
            q, k, v, n, SCALE)), [q, cache(BF16), cache(BF16), lens]
    if layout == "int8":
        return (lambda q, k, v, ks, vs, n: flash_decode_attention(
            q, k, v, n, SCALE, k_scale=ks, v_scale=vs)), \
            [q, cache(I8), cache(I8), scales, scales, lens]
    if layout == "paged":
        return (lambda q, k, v, bt, n: flash_decode_attention(
            q, k, v, n, SCALE, block_tables=bt)), \
            [q, pool(BF16), pool(BF16), tables, lens]
    assert layout == "hot_bf16"
    return (lambda q, k, v, kq, vq, ks, vs, bt, bq, n: flash_decode_attention(
        q, k, v, n, SCALE, k_quant=kq, v_quant=vq, k_scale=ks, v_scale=vs,
        block_tables=bt, block_quant=bq)), \
        [q, pool(BF16), pool(BF16), pool(I8), pool(I8), pscales, pscales,
         tables, tables, lens]


def _quant(m, k, n):
    return (lambda x, q, s: qm.quant_matmul_pallas(x, q, s)), \
        [((m, k), BF16), ((k, n), I8), ((n,), F32)]


DECODE_SHAPES = {"decode": (SLOTS, 1), "verify": (SLOTS, 5),
                 "chunk": (1, 256)}
CASES = {
    "flash_fwd": _flash_fwd,
    "flash_bwd": _flash_bwd,
    "flash_block_grads": _flash_block_grads,
    "flash_with_lse": _flash_with_lse,
    "rmsnorm_fwd": _rms_fwd,
    "rmsnorm_bwd": _rms_bwd,
    **{f"decode_{layout}_{name}":
       (lambda layout=layout, b=b, s=s: _decode(layout, b, s))
       for layout in ("contiguous", "int8", "paged", "hot_bf16")
       for name, (b, s) in DECODE_SHAPES.items()},
    "quant_matmul_up": lambda: _quant(8, HID, FFN),
    "quant_matmul_down": lambda: _quant(8, FFN, HID),
    "quant_matmul_head": lambda: _quant(8, HID, VOCAB),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{case}: the compiled program holds no Pallas kernel"
