"""Compile every main-path Pallas kernel for a described v5e at SmolLM-1.7B
widths (H 2048, 32/32 heads of 64, FFN 8192, vocab 49152, seq 2048, bf16),
and the serving programs themselves, to read off the compiled text that the
KV cache never leaves its buffer inside them.

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

import math
import os
import re

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


# --------------------------------------------------------------------------- #
# the serving programs: the KV cache never leaves its buffer
# --------------------------------------------------------------------------- #
#
# Compiled for the chip, a cache leaf fed through a ``lax.scan`` as xs/ys
# becomes, per layer, a fusion that slices the layer out into a buffer of
# its own and one that writes the whole layer back, and per decode step a
# copy of the whole stacked array into the step loop's carry (PERF.md,
# PR 26: 56 % of SmolLM's decode step). The engine keeps the leaves in the
# scan's carry and indexes them instead; these tests read the compiled
# text and the compiler's own byte counts to hold it to that.

SERVE_LAYERS, SERVE_SLOTS = 2, 4
SERVE_TAIL = (HEADS, D)
_SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")
_COMP = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{$")
_CALLED = re.compile(r"(?:body|condition|to_apply|calls)=%([\w.\-]+)")


def _elems(shape: str, tail=SERVE_TAIL) -> int:
    """Elements of the largest K/V-shaped array (dims end in ``tail``:
    kv heads, head size) in an HLO shape string; a tuple has several."""
    best = 0
    for dims in _SHAPE.findall(shape):
        dims = tuple(int(d) for d in dims.split(",") if d)
        if dims[-len(tail):] == tail:
            best = max(best, math.prod(dims))
    return best


def _computations(text: str) -> dict:
    """HLO text -> {computation: [(name, shape, op, rest of the line)]}."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = _COMP.match(line)
        if head:
            cur = comps[head.group(1)] = []
        elif cur is not None and _INSTR.match(line):
            cur.append(_INSTR.match(line).groups())
    return comps


def _cache_movers(text: str, layer_elems: int) -> list:
    """Instructions that run inside a loop of the program and move
    ``layer_elems`` elements of K or V or more: a ``copy``, a (fused)
    ``dynamic-slice`` that lands them in a buffer of their own, a (fused)
    ``dynamic-update-slice`` whose update they are. A fusion that READS a
    layer through a dynamic-slice and reduces it (the attention
    contractions), or scatters a few rows into the stacked array in place,
    is what the program should be made of and is not listed."""
    comps = _computations(text)
    shapes = {c: {n: sh for n, sh, _, _ in ins} for c, ins in comps.items()}

    def update_elems(comp, rest):
        # dynamic-update-slice(%operand, %update, %idx...): the update
        ops = re.findall(r"%([\w.\-]+)", rest)
        return _elems(shapes[comp].get(ops[1], "")) if len(ops) > 1 else 0

    def moves(comp, name, shape, op, rest):
        if op in ("copy", "dynamic-slice"):
            return _elems(shape) >= layer_elems
        if op == "dynamic-update-slice":
            return update_elems(comp, rest) >= layer_elems
        if op == "fusion":
            inner = _CALLED.search(rest).group(1)
            for n, sh, iop, irest in comps.get(inner, []):
                if iop == "dynamic-slice" and _elems(shape) >= layer_elems \
                        and _elems(sh) >= layer_elems:
                    return True
                if iop == "dynamic-update-slice" \
                        and update_elems(inner, irest) >= layer_elems:
                    return True
        return False

    # every computation a while loop runs, through calls and nested loops
    # but not into fusions (a fusion's inside never touches memory itself)
    todo = [c for ins in comps.values() for _, _, op, rest in ins
            if op == "while" for c in _CALLED.findall(rest)]
    looped = set()
    while todo:
        c = todo.pop()
        if c in looped or c not in comps:
            continue
        looped.add(c)
        todo += [x for _, _, op, rest in comps[c] if op != "fusion"
                 for x in _CALLED.findall(rest)]
    return [f"{c}: %{name} = {shape} {op}"
            for c in sorted(looped) for name, shape, op, rest in comps[c]
            if moves(c, name, shape, op, rest)]


def _serving_program(topo, prog, layout):
    """(lowered-and-compiled ``prog`` of a SERVE_LAYERS-layer engine at
    SmolLM's head geometry on one described chip, elements of one layer's
    K, bytes of the lane-padded cache)."""
    from picotron_tpu.config import Config
    from picotron_tpu.inference.engine import InferenceEngine
    from picotron_tpu.models import llama
    from picotron_tpu.topology import build_topology, named_shardings

    cfg = Config.from_dict({
        "model": dict(hidden_size=HID, intermediate_size=FFN,
                      num_attention_heads=HEADS, num_key_value_heads=HEADS,
                      vocab_size=VOCAB, num_hidden_layers=SERVE_LAYERS,
                      max_position_embeddings=SEQ, dtype="bfloat16"),
        "inference": {"kv_layout": layout, "kv_page_len": PAGE}})
    mesh = build_topology(1, 1, 1, 1, devices=topo.devices)
    eng = InferenceEngine(cfg, mesh, slots=SERVE_SLOTS, max_seq_len=SEQ)

    def abstract(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, named_shardings(mesh, specs))

    params = abstract(jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg.model)), eng._pspecs)
    cache = abstract(jax.eval_shape(eng._init_cache_jit), eng._cspecs)
    rep = named_shardings(mesh, jax.sharding.PartitionSpec())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    B = SERVE_SLOTS
    if prog == "decode_block":
        jitted = eng._program("decode_block")
        args = (arg((B,), I32), arg((eng.decode_block_len, 2), jnp.uint32),
                arg((B,), I32), arg((B,), I32), arg((B,), F32),
                arg((B,), I32), arg((B,), F32))
    else:
        jitted = eng._prefill_chunk_jit
        args = (arg((1, eng.prefill_chunk), I32),) + (arg((), I32),) * 3
        if eng.sample_on_device:
            args += (arg((2,), jnp.uint32), arg((1,), F32), arg((1,), I32),
                     arg((1,), F32))
    kv = cache["k"].shape  # [L, slots | pages, rows, Hkv, D]
    layer_elems = kv[1] * kv[2] * kv[3] * kv[4]
    padded = 2 * kv[0] * layer_elems // kv[4] * max(kv[4], 128) * 2
    return jitted.lower(params, cache, *args).compile(), layer_elems, padded


@pytest.mark.parametrize("prog,layout", [
    ("decode_block", "contiguous"), ("prefill_chunk", "contiguous"),
    ("decode_block", "paged"), ("prefill_chunk", "paged")])
def test_serving_program_leaves_cache_in_place(prog, layout, topo, one_chip):
    compiled, layer_elems, padded = _serving_program(topo, prog, layout)
    movers = _cache_movers(compiled.as_text(), layer_elems)
    assert not movers, (
        f"{prog}/{layout}: a loop of the compiled program moves a whole "
        "layer of the KV cache or more at once:\n" + "\n".join(movers))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.5 * padded, (
        f"{prog}/{layout}: {temp / 1e6:.0f} MB of temporaries against a "
        f"lane-padded cache of {padded / 1e6:.0f} MB")


# ---- the latent cache of the DeepSeek-V3.2 block (PR 28) -------------------


def _latent_program(topo, prog):
    """``prog`` of a 2-layer engine (one dense, one expert layer) at the
    published DeepSeek-V3.2 widths and the benchmark cell's cache geometry
    (8 slots x 24576), compiled for one described chip."""
    import json

    from picotron_tpu.config import Config
    from picotron_tpu.inference.engine import InferenceEngine
    from picotron_tpu.topology import build_topology, named_shardings

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "deepseek-v3.2-ep32-l7.json")) as f:
        pub = json.load(f)
    model = {k: pub[k] for k in pub["model_keys"]}
    model.update({k: pub[k] for k in (
        "num_attention_heads", "num_key_value_heads", "hidden_size",
        "intermediate_size", "vocab_size", "rms_norm_eps", "rope_theta",
        "max_position_embeddings")}, num_hidden_layers=2, dtype="bfloat16")
    cfg = Config.from_dict({"model": model,
                            "training": {"seq_length": 24576}})
    mesh = build_topology(1, 1, 1, 1, devices=topo.devices)
    eng = InferenceEngine(cfg, mesh, slots=8, max_seq_len=24576)

    def abstract(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            tree, named_shardings(mesh, specs))

    params = abstract(jax.eval_shape(
        lambda: eng.model.init_params(jax.random.key(0), cfg.model)),
        eng._pspecs)
    cache = abstract(jax.eval_shape(eng._init_cache_jit), eng._cspecs)
    rep = named_shardings(mesh, jax.sharding.PartitionSpec())
    arg = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    if prog == "decode_block":
        jitted = eng._program("decode_block")
        args = (arg((8,), I32), arg((eng.decode_block_len, 2), jnp.uint32),
                arg((8,), I32), arg((8,), I32), arg((8,), F32),
                arg((8,), I32), arg((8,), F32))
    else:
        jitted = eng._prefill_chunk_jit
        args = (arg((1, eng.prefill_chunk), I32),) + (arg((), I32),) * 3
    return jitted.lower(params, cache, *args).compile()


@pytest.mark.parametrize("prog", ["decode_block", "prefill_chunk"])
def test_latent_cache_is_row_major_and_never_copied(prog, topo, one_chip):
    """The TPU lays a cache leaf whose rows are not whole lanes (64 wide, or
    576) out with the tokens minor-most and copies it whole, in the layer
    loop or at the program's entry and exit (PR 28 read both here): the
    lane-padded ``[c_kv | k_r]`` row stays row-major, and no instruction
    copies a whole leaf; nor is a layer's slice of the experts' stacks
    copied out before the loop over experts."""
    text = _latent_program(topo, prog).as_text()
    leaf = r"bf16\[2,8,24576,(?:640|128)\]"
    lines = text.splitlines()
    copies = [l.strip()[:160] for l in lines
              if re.search(rf"= {leaf}\S* copy\(", l)]
    assert not copies, "\n".join(copies)
    params = [l for l in lines if re.search(rf"cache__c?k[vi]__\S* = {leaf}", l)
              and " parameter(" in l]
    assert len(params) == 2, params
    assert all("{3,2,1,0" in l for l in params), params
    sliced = [l.strip()[:160] for l in lines
              if re.search(r"= bf16\[(?:1,)?8,(?:7168,2048|2048,7168)\]", l)
              and " parameter(" not in l and "get-tuple-element" not in l]
    assert not sliced, "\n".join(sliced)
