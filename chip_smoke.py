#!/usr/bin/env python3
"""The quickest proof that picotron-tpu still starts on the chip.

``python chip_smoke.py`` (one TPU chip) drives the normal entry points at
the full width and depth of SmolLM-1.7B (24 layers, bf16, random weights
from a seed, no network) and checks what comes out:

- *kernels*: every main-path Pallas kernel, compiled, against its XLA oracle
  at SmolLM head geometry;
- *train*: ``picotron_tpu.train.main`` (what ``python train.py --config``
  calls) for 8 optimizer steps on a config this script writes;
- *serve*: ``tools.serve.Server`` over HTTP, 4 slots x 2048, once per
  ``inference.attend_impl`` (``dense``, then ``flash``), plus a logits
  comparison of the engine's prefill/decode against the full forward.

``python chip_smoke.py --four-chip`` (four chips, run by the builder, never
by the driver) runs only the parallel-training path and what it is compared
with: dp2 x tp2 (sequence parallel) and pp2 x cp2 (1f1b + ring attention)
against the same job on one of the four devices.

Processes: this parent NEVER imports jax. Each phase is a child process
(``--phase NAME``), run strictly one after another, so the chip belongs to
one process at a time and each phase starts with empty device memory (the
14.5 GB training state and the server do not fit the chip together). The
children share the persistent compile cache (``utils.enable_compile_cache``:
``JAX_COMPILATION_CACHE_DIR`` if set, else ``<repo>/.jax_cache``). The one
four-chip phase is one process that drives all four chips and frees each
run's state before the next.

Any failed check, exception, missing phase or non-TPU device is a non-zero
exit and no ``"ok": true``. ``--rehearse`` (asked for explicitly) shrinks
the model and pins the CPU to exercise the control flow; it says so, refuses
to run on anything but the CPU platform, and so can never print
``"ok": true`` with a ``tpu`` device. The last line of stdout is the
contract's: ``{"ok": true, "device": {"platform": ..., "kind": ...,
"count": ...}}``. Throughput, MFU, memory and latency lines are smoke
readings from one run, not measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke")  # configs + IR dumps; gitignored
RESULT_TAG = "CHIP_SMOKE_PHASE_RESULT "
ONE_CHIP_PHASES = ("kernels", "train", "serve")
FOUR_CHIP_PHASES = ("mesh",)
PHASE_TIMEOUT_S = 1000

# bf16 tolerances, stated once. Kernels: the oracle runs the same math
# through XLA with bf16 operands and fp32 statistics, so outputs agree to
# bf16 resolution (2^-8 relative) of O(1) values; gradients double it.
TOL_FWD = 2e-2
TOL_BWD = 3e-2
# Serving logits: prefill/decode through the KV cache vs the full forward,
# both bf16 programs over the same weights but with different reduction
# orders over 24 layers — compared in absolute terms against the logits'
# own scale (max |logit|), plus exact agreement of the argmax where the
# oracle's top-2 margin exceeds the tolerance.
TOL_LOGITS_REL = 3e-2
# Four-chip loss agreement: the fp32 invariant is 3e-5 (SKILL.md "Oracle");
# in bf16 each topology rounds differently (tp splits the matmul
# reductions, cp the softmax), which moves a ~10.9 loss in its fourth digit
# and compounds through the optimizer over 4 steps (seen on four chips, one
# run: 2.9e-3 for dp2 x tp2, 1.3e-3 for pp2 x cp2, both at step 4).
TOL_LOSS_4CHIP = 2e-2


# --------------------------------------------------------------------------- #
# configs (written by the smoke; nothing is read from configs/)
# --------------------------------------------------------------------------- #


def model_dict(rehearse: bool) -> dict:
    if rehearse:
        return dict(name="rehearsal-tiny", num_hidden_layers=2,
                    num_attention_heads=4, num_key_value_heads=4,
                    hidden_size=128, intermediate_size=256, vocab_size=512,
                    max_position_embeddings=256, dtype="float32",
                    attention_impl="auto")
    from picotron_tpu.config import SMOLLM_1_7B

    return dict(SMOLLM_1_7B)  # 24 layers, H2048, 32x64 heads, bf16


def train_config(rehearse: bool, *, dp=1, pp=1, cp=1, tp=1, mbs=4, acc=1,
                 steps=8, **dist) -> dict:
    seq = 128 if rehearse else 2048
    training = {"seq_length": seq, "micro_batch_size": mbs,
                "gradient_accumulation_steps": acc, "remat": "full",
                "learning_rate": 3e-4, "total_train_steps": steps,
                "seed": 42}
    if pp == 1:
        training["grad_accum_dtype"] = "param"  # the r01/r02 point
    return {
        "distributed": {"dp_size": dp, "pp_size": pp, "cp_size": cp,
                        "tp_size": tp, "use_cpu": rehearse, **dist},
        "model": model_dict(rehearse),
        "training": training,
        "dataset": {"name": "synthetic"},
        "logging": {"log_frequency": 1},
    }


def serve_config(rehearse: bool, attend_impl: str) -> dict:
    cfg = train_config(rehearse)
    # no flash->dense degradation: a kernel that fails must fail the smoke
    cfg["inference"] = {"attend_impl": attend_impl, "attend_fallback": False}
    return cfg


def write_config(name: str, cfg: dict) -> str:
    os.makedirs(WORK, exist_ok=True)
    path = os.path.join(WORK, name)
    with open(path, "w") as f:
        json.dump(cfg, f, indent=1)
    return path


# --------------------------------------------------------------------------- #
# child-side helpers (jax is imported only below this line, in children)
# --------------------------------------------------------------------------- #


def _device(rehearse: bool) -> dict:
    """First thing every phase does: name the device, refuse the wrong one."""
    from picotron_tpu.utils import device_record

    dev = device_record()
    print(f"device: {json.dumps(dev)}", flush=True)
    want = "cpu" if rehearse else "tpu"
    if dev["platform"] != want:
        raise SystemExit(
            f"chip_smoke: platform is {dev['platform']!r}, need {want!r}"
            + ("" if rehearse else " (no accelerator; use --rehearse for "
                                   "the CPU control-flow rehearsal)"))
    return dev


def _start_phase(name: str, rehearse: bool) -> dict:
    import jax

    from picotron_tpu import native
    from picotron_tpu.utils import enable_compile_cache

    dev = _device(rehearse)
    print(f"[{name}] compile cache: {enable_compile_cache()}", flush=True)
    print(f"[{name}] data loader: {native.loader()}", flush=True)
    ir = os.path.join(WORK, "ir", name)
    if os.path.isdir(ir):
        for f in os.listdir(ir):
            os.unlink(os.path.join(ir, f))
    # every program JAX lowers in this phase is dumped here; _kernels_held
    # reads them back to show which compiled Pallas kernels each one holds
    jax.config.update("jax_dump_ir_to", ir)
    return dev


def _kernels_held(name: str, rehearse: bool, need: dict) -> None:
    """Print, per lowered program of this phase, the Pallas kernels it holds
    (``tpu_custom_call`` sites by kernel name), and require ``need``:
    {program-name substring: [kernel names]}. On the chip this is the proof
    that the compiled kernel — not interpret mode, not the XLA twin — ran."""
    ir = os.path.join(WORK, "ir", name)
    held = {}
    for f in sorted(os.listdir(ir)) if os.path.isdir(ir) else []:
        with open(os.path.join(ir, f), errors="replace") as fh:
            text = fh.read()
        if "tpu_custom_call" not in text:
            continue
        prog = re.sub(r"^jax_ir\d+_|_compile\.mlir$", "", f)
        names = re.findall(r'kernel_name = "([^"]+)"', text)
        counts = held.setdefault(prog, {})
        for k in names:
            counts[k] = counts.get(k, 0) + 1
    for prog, counts in held.items():
        print(f"[{name}] program {prog} holds "
              + ", ".join(f"{k} x{n}" for k, n in sorted(counts.items())),
              flush=True)
    if rehearse:
        print(f"[{name}] REHEARSAL: kernels ran interpreted or as XLA; "
              f"custom-call check skipped", flush=True)
        return
    for prog_sub, kernels in need.items():
        found = set()
        for prog, counts in held.items():
            if prog_sub in prog:
                found |= set(counts)
        missing = [k for k in kernels if k not in found]
        if missing:
            raise SystemExit(
                f"[{name}] no lowered program matching {prog_sub!r} holds "
                f"the compiled kernel(s) {missing}; found {sorted(found)}")


class _Tee:
    """Pass a stream through and keep a copy (the trainer's own log lines
    are the smoke's source of losses and readings)."""

    def __init__(self, stream):
        self.stream, self.lines = stream, []
        self._buf = ""

    def write(self, s):
        self.stream.write(s)
        self._buf += s
        *done, self._buf = self._buf.split("\n")
        self.lines += done
        return len(s)

    def flush(self):
        self.stream.flush()

    def __getattr__(self, name):  # isatty, fileno, ... of the real stream
        return getattr(self.stream, name)

    def close(self):  # a logging handler may hold us past the redirect
        pass


def _run_trainer(cfg: dict, name: str) -> dict:
    """One ``picotron_tpu.train.main`` run; returns its parsed log."""
    import contextlib
    import gc

    import jax

    from picotron_tpu import train as train_mod

    path = write_config(f"{name}.json", cfg)
    out, err = _Tee(sys.stdout), _Tee(sys.stderr)
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = train_mod.main(["--config", path])
    wall = time.perf_counter() - t0
    if rc != 0:
        raise SystemExit(f"[{name}] train.main returned {rc}")
    steps = []
    for line in out.lines:
        m = re.search(r"Step:\s*(\d+)\s*\|\s*Loss:\s*([-\w.]+)", line)
        if not m:
            continue
        row = {"step": int(m.group(1)), "loss": float(m.group(2)),
               "line": line}
        for key, pat in (("tok_s_chip", r"Tokens/s/chip:\s*([\d.]+)([KMBT]?)"),
                         ("mfu", r"MFU:\s*([\d.]+)%"),
                         ("mem_gb", r"Memory usage:\s*([\d.]+)GB")):
            mm = re.search(pat, line)
            if mm:
                mult = {"": 1, "K": 1e3, "M": 1e6, "B": 1e9, "T": 1e12}[
                    mm.group(2) if key == "tok_s_chip" else ""]
                row[key] = float(mm.group(1)) * mult
        steps.append(row)
    comm = {}
    for line in err.lines:
        m = re.match(r"\[comm\] (.+?) axis=(.+?) shape=", line)
        if m:
            k = f"{m.group(1)}@{m.group(2)}"
            comm[k] = comm.get(k, 0) + 1
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in jax.local_devices()]
    # free this run's state before the next: main() holds no arrays after
    # it returns; drop the compiled programs and collect what is left
    jax.clear_caches()
    gc.collect()
    return {"steps": steps, "wall_s": wall, "comm": comm, "peak_bytes": mem}


def _check_losses(name: str, steps: list, n: int, falling: bool) -> list:
    import math

    losses = [s["loss"] for s in steps]
    if len(losses) < n:
        raise SystemExit(f"[{name}] {len(losses)} steps logged, need {n}")
    if not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"[{name}] non-finite loss in {losses}")
    if falling and not losses[-1] < losses[0] - 0.25:
        raise SystemExit(
            f"[{name}] loss did not fall clearly: first {losses[0]:.4f}, "
            f"last {losses[-1]:.4f} (need a drop of more than 0.25)")
    print(f"[{name}] losses: " + " ".join(f"{x:.4f}" for x in losses),
          flush=True)
    return losses


# --------------------------------------------------------------------------- #
# phase: kernels
# --------------------------------------------------------------------------- #


def phase_kernels(rehearse: bool) -> dict:
    dev = _start_phase("kernels", rehearse)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from picotron_tpu.inference import kv_cache
    from picotron_tpu.ops.attention import block_attention, sdpa
    from picotron_tpu.ops.pallas import quant_matmul as qm
    from picotron_tpu.ops.pallas.decode_attention import (
        flash_decode_attention, flash_decode_stacked)
    from picotron_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_with_lse, flash_block_grads)
    from picotron_tpu.ops.pallas.rmsnorm import rms_norm_pallas
    from picotron_tpu.ops.rmsnorm import rms_norm

    interp = rehearse  # interpret mode ONLY in the CPU rehearsal
    dt = jnp.float32 if rehearse else jnp.bfloat16
    B, S, H, D = (1, 256, 4, 32) if rehearse else (2, 2048, 32, 64)
    hid = H * D
    scale = D ** -0.5
    f32 = lambda x: np.asarray(x, np.float32)

    def close(what, got, want, tol):
        got, want = f32(got), f32(want)
        err = float(np.max(np.abs(got - want)))
        ref = float(np.max(np.abs(want)))
        ok = np.allclose(got, want, rtol=tol, atol=tol * max(ref, 1.0))
        print(f"[kernels] {what}: max|err| {err:.3e} (ref max {ref:.3e}, "
              f"tol {tol:g}) {'ok' if ok else 'FAIL'}", flush=True)
        if not ok:
            raise SystemExit(f"[kernels] {what} disagrees with its oracle")

    def rnd(seed, shape, dtype=None):
        return jax.random.normal(jax.random.PRNGKey(seed), shape,
                                 jnp.float32).astype(dtype or dt)

    # --- flash attention fwd / bwd / ring block grads vs the einsum paths
    q, k, v = (rnd(i, (B, S, H, D)) for i in range(3))
    if rehearse:
        # off-chip the training kernels have no interpret switch of their
        # own; the CPU suite (tests/test_pallas_kernels.py) covers them
        print("[kernels] REHEARSAL: flash/rmsnorm training kernels are "
              "chip-only here", flush=True)
    else:
        flash = lambda q, k, v: flash_attention(q, k, v, scale)
        ref = lambda q, k, v: sdpa(q, k, v, scale, causal=True)
        close("flash fwd vs sdpa", jax.jit(flash)(q, k, v),
              jax.jit(ref)(q, k, v), TOL_FWD)
        loss = lambda f: (lambda q, k, v:
                          (f(q, k, v).astype(jnp.float32) ** 2).mean())
        gf = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
        for a, b, n in zip(gf, gr, "qkv"):
            close(f"flash bwd d{n} vs sdpa", a, b, TOL_BWD)
        out, lse = jax.jit(lambda q, k, v: flash_attention_with_lse(
            q, k, v, scale, causal=False))(q, k, v)
        do = rnd(3, out.shape, out.dtype)
        gb = jax.jit(lambda *a: flash_block_grads(
            *a, scale, causal=False))(q, k, v, out, lse, do)

        def ring_ref(q, k, v):
            o, _ = block_attention(q, k, v, scale, mask=None)
            return (o.astype(jnp.float32) * do.astype(jnp.float32)).sum()

        gr = jax.jit(jax.grad(ring_ref, argnums=(0, 1, 2)))(q, k, v)
        for a, b, n in zip(gb, gr, "qkv"):
            close(f"flash_block_grads d{n} vs einsum block", a, b, TOL_BWD)
        # --- RMSNorm fwd / bwd
        x = rnd(4, (4, S, hid))
        w = (1.0 + 0.1 * rnd(5, (hid,), jnp.float32)).astype(dt)
        close("rmsnorm fwd", jax.jit(rms_norm_pallas)(x, w),
              jax.jit(rms_norm)(x, w), TOL_FWD)
        nl = lambda f: (lambda x, w:
                        (f(x, w, 1e-5).astype(jnp.float32) ** 2).mean())
        gx, gw = jax.jit(jax.grad(nl(rms_norm_pallas), argnums=(0, 1)))(x, w)
        rx, rw = jax.jit(jax.grad(nl(rms_norm), argnums=(0, 1)))(x, w)
        close("rmsnorm bwd dx", gx, rx, TOL_BWD)
        close("rmsnorm bwd dw", gw, rw, TOL_BWD)

    # --- decode attention, every layout the engine can select, vs dense
    slots, T, page = (4, 128, 16) if rehearse else (8, 2048, 64)
    maxp = T // page
    npages = slots * maxp + 1
    rng = np.random.default_rng(0)
    kc, vc = rnd(6, (slots, T, H, D)), rnd(7, (slots, T, H, D))
    kq, ks = kv_cache.quantize_kv(kc)
    vq, vs = kv_cache.quantize_kv(vc)
    kd, vd = (kv_cache.dequantize_kv(a, s, jnp.float32)
              for a, s in ((kq, ks), (vq, vs)))
    # paged twins: slot b's page j lives at a shuffled pool row
    tables = rng.permutation(np.arange(1, npages)).reshape(slots, maxp)
    tables = jnp.asarray(tables, jnp.int32)

    def pool(x):  # [slots, T, ...] -> [npages, page, ...] under `tables`
        pages = x.reshape((slots * maxp, page) + x.shape[2:])
        out = jnp.zeros((npages, page) + x.shape[2:], x.dtype)
        return out.at[tables.reshape(-1)].set(pages)

    page_quant = jnp.asarray(rng.integers(0, 2, npages), jnp.int32)
    flags = jnp.take(page_quant, tables, axis=0)  # [slots, maxp]
    cold = jnp.repeat(flags != 0, page, axis=1)[..., None, None]
    km = jnp.where(cold, kd, kc.astype(jnp.float32))
    vm = jnp.where(cold, vd, vc.astype(jnp.float32))
    layouts = {
        "contiguous": (dict(k=kc, v=vc), (kc, vc)),
        "int8": (dict(k=kq, v=vq, k_scale=ks, v_scale=vs), (kd, vd)),
        "paged": (dict(k=pool(kc), v=pool(vc), block_tables=tables),
                  (kc, vc)),
        "hot_bf16": (dict(k=pool(kc), v=pool(vc), k_quant=pool(kq),
                          v_quant=pool(vq), k_scale=pool(ks),
                          v_scale=pool(vs), block_tables=tables,
                          block_quant=flags), (km, vm)),
    }
    chunk = 32 if rehearse else 256
    shapes = {"decode S=1": (slots, 1), "verify S=5": (slots, 5),
              f"prefill chunk S={chunk}": (1, chunk)}
    for lname, (kw, (dk, dv)) in layouts.items():
        for sname, (b, s) in shapes.items():
            qd = rnd(8, (b, s, H, D))
            lens = jnp.asarray(
                [T, T // 3 + s, s, T - 1, 2 * s + 1, T // 2, s + 7,
                 T - 5][:b] if b > 1 else [T // 2 + s], jnp.int32)
            paged = "block_tables" in kw  # pools are shared, tables per slot
            sel = {n: (a if paged and not n.startswith("block_") else a[:b])
                   for n, a in kw.items()}
            kk, vv = sel.pop("k"), sel.pop("v")
            got = jax.jit(lambda qd, kk, vv, lens, sel: flash_decode_attention(
                qd, kk, vv, lens, scale, interpret=interp, **sel))(
                    qd, kk, vv, lens, sel)
            want = jax.jit(lambda qd, dk, dv, lens: kv_cache.decode_attention(
                qd, dk, dv, lens, scale))(qd, dk[:b], dv[:b], lens)
            close(f"decode attention {lname} {sname} vs dense", got, want,
                  TOL_FWD)

    # --- the S == 1 step on the stacked leaf as ``attend_impl: auto`` runs
    # it on a chip, at both pack factors: SmolLM's rows (two heads of 64)
    # and Mistral's (a head of 128, four query heads each), a traced layer
    layers = 2 if rehearse else 3
    lens = jnp.asarray([T, T // 3 + 1, 0, T - 1, 3, T // 2, 8, T - 5][:slots],
                       jnp.int32)
    live = np.asarray(lens) > 0
    for what, heads, kv_heads, d in (("two heads of 64 a row", 32, 32, 64),
                                     ("a head of 128 a row", 32, 8, 128)):
        if rehearse:
            heads, kv_heads = heads // 4, kv_heads // 4
        pk = kv_cache.pack_factor(d, kv_heads)
        leaf = (layers, slots, T, kv_heads // pk, pk * d)
        qd, kl, vl = rnd(11, (slots, 1, heads, d)), rnd(12, leaf), \
            rnd(13, leaf)
        got = jax.jit(lambda qd, kl, vl, lens, layer: flash_decode_stacked(
            qd, kl, vl, lens, d ** -0.5, layer, interpret=interp))(
                qd, kl, vl, lens, jnp.int32(layers - 1))
        want = jax.jit(lambda qd, kl, vl, lens: kv_cache.decode_attention(
            qd, kl[layers - 1], vl[layers - 1], lens, d ** -0.5))(
                qd, kl, vl, lens)
        close(f"decode attention stacked leaf, {what}, vs dense",
              f32(got)[live], f32(want)[live], TOL_FWD)
        if np.any(f32(got)[~live] != 0.0):
            raise SystemExit("[kernels] a free slot's rows are not zeros")

    # --- int8 weight matmul vs its XLA twin, the three SmolLM projections
    for m_, k_, n_ in ((8, hid, 4 * hid), (8, 4 * hid, hid),
                       (8, hid, 512 if rehearse else 49152)):
        xq = rnd(9, (m_, k_))
        wq = qm.quantize_weight(rnd(10, (k_, n_), jnp.float32))
        got = jax.jit(lambda x, q, s: qm.quant_matmul_pallas(
            x, q, s, interpret=interp))(xq, wq["q"], wq["s"])
        want = jax.jit(qm.quant_matmul_xla)(xq, wq["q"], wq["s"])
        close(f"quant_matmul ({m_},{k_})x({k_},{n_}) vs xla", got, want,
              TOL_FWD)

    _kernels_held("kernels", rehearse, {"": [
        "flash_fwd", "flash_bwd_dkv", "rmsnorm_fwd",
        "rmsnorm_bwd", "flash_decode_attention", "quant_matmul"]})
    return {"device": dev}


# --------------------------------------------------------------------------- #
# phase: train
# --------------------------------------------------------------------------- #

# The r01/r02 point was micro-batch 4 x seq 2048, remat full, grads in bf16.
# Under jax 0.9.0 / libtpu 0.0.34 the TPU compiler refuses that step program
# for one 16 GB v5e by 62 MB (15.81G of 15.75G; a third of its 5.68G of
# temporaries is fragmentation — tests/test_chip_compile.py keeps no such
# compile, it takes a quarter of a minute), so the smoke trains at the
# largest micro-batch that does fit and says so.
TRAIN_MBS = 3
TRAIN_STEPS = 8


def phase_train(rehearse: bool) -> dict:
    dev = _start_phase("train", rehearse)
    cfg = train_config(rehearse, mbs=TRAIN_MBS, steps=TRAIN_STEPS)
    print(f"[train] SmolLM-1.7B x {cfg['model']['num_hidden_layers']} layers"
          f", micro-batch {TRAIN_MBS} (the largest that fits the chip on this "
          f"JAX; the r01/r02 point was 4) x seq "
          f"{cfg['training']['seq_length']}, remat full, "
          f"{TRAIN_STEPS} steps" + (" — REHEARSAL model" if rehearse else ""),
          flush=True)
    run = _run_trainer(cfg, "train")
    _check_losses("train", run["steps"], TRAIN_STEPS, falling=True)
    first, last = run["steps"][0], run["steps"][-1]
    tokens = cfg["training"]["seq_length"] * TRAIN_MBS
    if "tok_s_chip" in first and "tok_s_chip" in last:
        print(f"[train] first step (compile included) "
              f"{tokens / first['tok_s_chip']:.1f} s; steady step "
              f"{tokens / last['tok_s_chip']:.3f} s; whole run "
              f"{run['wall_s']:.1f} s", flush=True)
    print(f"[train] smoke reading, one run: tokens/s/chip "
          f"{last.get('tok_s_chip', 'n/a')}, MFU {last.get('mfu', 'n/a')} %"
          f", peak HBM {last.get('mem_gb', 'n/a')} GB", flush=True)
    if not rehearse and "mfu" not in last:
        raise SystemExit("[train] the trainer logged no MFU on the chip")
    _kernels_held("train", rehearse, {"jit__step": [
        "flash_fwd", "flash_bwd_dkv", "rmsnorm_fwd",
        "rmsnorm_bwd"]})
    return {"device": dev}


# --------------------------------------------------------------------------- #
# phase: serve
# --------------------------------------------------------------------------- #


def _oracle_logits(cfg, engine, params, seq):
    """Full-sequence logits [S, V] from llama.forward_logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from picotron_tpu.models import llama
    from picotron_tpu.utils import shard_map

    fwd = jax.jit(shard_map(
        lambda p, t: llama.forward_logits(p, t, cfg), engine.topo.mesh,
        in_specs=(llama.param_pspecs(cfg.model), P()), out_specs=P()))
    # pad to a multiple of 128 like the engine's own prefill buckets: the
    # training flash kernel takes no ragged sequence length on the chip, and
    # under the causal mask the pad cannot reach the rows that are compared
    n = len(seq)
    toks = np.zeros((1, -(-n // 128) * 128), np.int32)
    toks[0, :n] = seq
    return np.asarray(fwd(params, jnp.asarray(toks)), np.float32)[0, :n]


def _logits_check(tag, cfg, engine, params, prompt, n_decode=4):
    """Prefill + ``n_decode`` decode steps through the engine's KV cache vs
    the full forward over the same (teacher-forced) sequence."""
    import jax
    import numpy as np

    seq = list(prompt)
    kv, last = engine.prefill(params, prompt)
    rows = [np.asarray(last, np.float32)[0]]
    cache = engine.insert(engine.init_cache(), kv, 0, len(prompt))
    slots = engine.slots
    for _ in range(n_decode):
        seq.append(int(np.argmax(rows[-1])))
        toks = np.zeros(slots, np.int32)
        toks[0] = seq[-1]
        cache, _, logits = engine.decode_step(
            params, cache, toks, jax.random.PRNGKey(0),
            np.zeros(slots, np.float32), np.zeros(slots, np.int32),
            np.ones(slots, np.float32))
        rows.append(np.asarray(logits, np.float32)[0])
    del cache, kv
    want = _oracle_logits(cfg, engine, params, seq)[len(prompt) - 1:]
    for i, (got, ref) in enumerate(zip(rows, want)):
        scale_ = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(got - ref)))
        top2 = np.sort(ref)[-2:]
        margin = float(top2[1] - top2[0])
        what = "prefill" if i == 0 else f"decode +{i}"
        ok = err <= TOL_LOGITS_REL * scale_
        if margin > 2 * TOL_LOGITS_REL * scale_:
            ok = ok and int(np.argmax(got)) == int(np.argmax(ref))
        print(f"[serve:{tag}] logits {what}: max|err| {err:.4f} vs "
              f"max|logit| {scale_:.3f} (tol {TOL_LOGITS_REL:g} x scale), "
              f"oracle top-2 margin {margin:.4f} {'ok' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            raise SystemExit(f"[serve:{tag}] {what} logits disagree with "
                             f"llama.forward_logits")
    return int(np.argmax(want[0]))


def _post(port: int, spec: dict):
    """POST /generate -> (status, body). The timeout covers the compiles a
    first request of each prompt bucket waits behind."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    conn.request("POST", "/generate", json.dumps(spec),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = (resp.status, json.loads(resp.read() or b"{}"))
    conn.close()
    return out


# 8 slots x 2048 does not fit one 16 GB v5e: the engine's decode_block
# program (default decode_block_len 8) is refused by the TPU compiler at
# 18.69G of 15.75G — the bf16 cache's 64-wide head dim is lane-padded to 128
# (2x) and the block's scan copies K and V whole (6 GB of copies, half of
# the temporaries fragmentation). 4 slots compile and run; PERF.md queues
# the cache layout for the decode work.
SERVE_SLOTS = 4


def _serve_once(rehearse: bool, attend_impl: str) -> None:
    import gc
    import threading

    import numpy as np

    from picotron_tpu.tools import serve

    tag = attend_impl
    cfg_path = write_config(f"serve_{attend_impl}.json",
                            serve_config(rehearse, attend_impl))
    max_len = 256 if rehearse else 2048
    n_new = 8 if rehearse else 64
    args = argparse.Namespace(
        smoke=False, config=cfg_path, load_path="", hf_path="",
        random_init=True, seed=0, slots=SERVE_SLOTS, max_seq_len=max_len,
        kv_layout=None, role=None, overlap=False, tenant_manifest="")
    t0 = time.perf_counter()
    cfg, engine, params, registry = serve._build_engine_and_params(args)
    if engine.attend_impl != attend_impl:
        raise SystemExit(f"[serve:{tag}] engine built with attend_impl "
                         f"{engine.attend_impl!r}")
    rng = np.random.default_rng(7)
    vocab = cfg.model.vocab_size
    lens = (4, 64, 180) if rehearse else (32, 512, 1500)
    prompts = {n: [int(t) for t in rng.integers(1, vocab, n)] for n in lens}
    short = prompts[lens[0]]
    # the engine-level comparison runs BEFORE the server owns a cache, so
    # the chip never holds two caches beside the weights
    oracle_tok = _logits_check(tag, cfg, engine, params, short)
    gc.collect()

    server = serve.Server(engine, params, port=0, seed=0, tenants=registry)
    server.start()
    try:
        port = server.port
        print(f"[serve:{tag}] server up on 127.0.0.1:{port} after "
              f"{time.perf_counter() - t0:.1f} s (engine + weights + logits "
              f"check)", flush=True)
        spec = lambda p: {"prompt": p, "max_new_tokens": n_new,
                          "temperature": 0.0}
        t1 = time.perf_counter()
        status, first = _post(port, spec(short))
        print(f"[serve:{tag}] first request (compile included): "
              f"{time.perf_counter() - t1:.1f} s", flush=True)
        mix = [lens[0], lens[1], lens[2], lens[0], lens[1]]

        def wave():
            """The mix posted at once; (seconds, [(n, status, body)])."""
            got = {}

            def client(i, n):
                got[i] = (n, *_post(port, spec(prompts[n])))

            threads = [threading.Thread(target=client, args=(i, n))
                       for i, n in enumerate(mix)]
            t = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join(900)
            if len(got) != len(mix):
                raise SystemExit(f"[serve:{tag}] {len(mix) - len(got)} "
                                 f"concurrent requests never returned")
            return time.perf_counter() - t, list(got.values())

        wall, results = wave()  # compiles each prompt bucket on the way
        # the same mix again is the steady smoke reading
        wall2, results2 = wave()
        everything = [(lens[0], status, first)] + results + results2
        for n, st, body in everything:
            if st != 200 or len(body.get("tokens", ())) != n_new \
                    or body.get("finish_reason") != "length":
                raise SystemExit(
                    f"[serve:{tag}] request with a {n}-token prompt: status "
                    f"{st}, {len(body.get('tokens', ()))} tokens, "
                    f"finish_reason {body.get('finish_reason')!r}")
        if first["tokens"][0] != oracle_tok:
            raise SystemExit(
                f"[serve:{tag}] first served token {first['tokens'][0]} != "
                f"argmax of forward_logits {oracle_tok}")
        same = [b["tokens"] for n, _, b in everything if n == lens[0]]
        if any(t != same[0] for t in same):
            raise SystemExit(f"[serve:{tag}] greedy streams of the same "
                             f"prompt differ across requests")
        print(f"[serve:{tag}] {len(everything)} requests completed with "
              f"{n_new} tokens each; first token == forward_logits argmax "
              f"({oracle_tok}); repeated prompts gave identical streams",
              flush=True)
        st, metrics = serve._get_text(port, "/metrics")
        if st != 200 or "picotron_" not in metrics:
            raise SystemExit(f"[serve:{tag}] GET /metrics: {st}")
        st, statz = serve._get(port, "/statz")
        if st != 200 or statz.get("dead") or statz.get("stalled"):
            raise SystemExit(f"[serve:{tag}] GET /statz: {st} {statz}")
        ttfts = sorted(b["ttft_s"] for _, _, b in results2)
        print(f"[serve:{tag}] /metrics and /statz answer; smoke reading, one"
              f" run: {len(mix)} concurrent requests ({len(mix) * n_new} new"
              f" tokens) in {wall2:.2f} s warm ({wall:.1f} s with compiles)"
              f" = {len(mix) * n_new / wall2:.1f} tokens/s; TTFT min/max "
              f"{ttfts[0]:.3f}/{ttfts[-1]:.3f} s", flush=True)
    finally:
        server.drain_and_join(timeout=60)
    if engine.attend_impl != attend_impl or server.front.dead:
        raise SystemExit(f"[serve:{tag}] engine ended on attend_impl "
                         f"{engine.attend_impl!r}, dead={server.front.dead}")
    del server, engine, params
    gc.collect()


def phase_serve(rehearse: bool) -> dict:
    import gc

    import jax

    dev = _start_phase("serve", rehearse)
    for impl in ("dense", "flash"):
        _serve_once(rehearse, impl)
        jax.clear_caches()
        gc.collect()
    # the flash engine's decode/prefill-chunk programs must hold the kernel
    _kernels_held("serve", rehearse,
                  {"": ["flash_decode_attention"]})
    return {"device": dev}


# --------------------------------------------------------------------------- #
# phase: mesh (four chips)
# --------------------------------------------------------------------------- #


def phase_mesh(rehearse: bool) -> dict:
    os.environ["PICOTRON_VERBOSE"] = "1"  # comm_trace: one line/collective
    if rehearse:
        os.environ["XLA_FLAGS"] = (  # last flag wins over an inherited one
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
    dev = _start_phase("mesh", rehearse)
    if dev["count"] != 4:
        raise SystemExit(f"[mesh] needs 4 devices, found {dev['count']}")
    steps = 4
    # Global batch: 2 sequences of 2048, the most one 16 GB device takes as
    # the comparison run on this JAX (micro-batch 4 misses by 62 MB, and any
    # gradient accumulation adds a 3.4 GB accumulator: 2 x mbs 2 is refused
    # at 17.98G of 15.75G). Each mesh splits the 2 sequences as it needs.
    runs = {
        # the meshes run FIRST: a device's peak_bytes_in_use only ever
        # grows, so the spread over the four devices is read before the
        # one-device run puts the whole state on device 0
        "dp2_tp2": train_config(rehearse, dp=2, tp=2, mbs=1, steps=steps,
                                tp_sequence_parallel=True),
        # 1f1b needs micro-batches to pipeline: 2 sequences as 2 x mbs 1
        "pp2_cp2": train_config(rehearse, pp=2, cp=2, mbs=1, acc=2,
                                steps=steps, pp_engine="1f1b",
                                cp_impl="ring"),
        # what they are compared with: the same job on one of the devices
        "one_device": train_config(rehearse, mbs=2, steps=steps),
    }
    out = {}
    for name, cfg in runs.items():
        if cfg["distributed"]["use_cpu"]:
            # the rehearsal's 4 virtual devices are already set up; the
            # trainer's own use_cpu path would re-pin them to world_size
            cfg["distributed"]["use_cpu"] = False
        d = cfg["distributed"]
        print(f"[mesh] {name}: dp{d['dp_size']} pp{d['pp_size']} "
              f"cp{d['cp_size']} tp{d['tp_size']}, global batch 2 x "
              f"{cfg['training']['seq_length']}, {steps} steps", flush=True)
        out[name] = _run_trainer(cfg, f"mesh_{name}")
        out[name]["losses"] = _check_losses(
            f"mesh:{name}", out[name]["steps"], steps, falling=False)
        print(f"[mesh] {name}: comm_trace counts "
              f"{json.dumps(out[name]['comm'], sort_keys=True)}", flush=True)
        peaks = out[name]["peak_bytes"]
        print(f"[mesh] {name}: peak bytes per device so far "
              f"{[round(b / 1e9, 2) for b in peaks]} GB", flush=True)
        if not rehearse and name != "one_device" and not (
                min(peaks) > 1e9 and min(peaks) > 0.5 * max(peaks)):
            # topology takes "the first dp*pp*cp*tp devices": all four
            # must hold a like share, none of it may sit on the first
            raise SystemExit(f"[mesh] {name}: state is not spread over the "
                             f"four devices: {peaks}")
    base = out["one_device"]["losses"]
    tol = 3e-5 if rehearse else TOL_LOSS_4CHIP
    for name in ("dp2_tp2", "pp2_cp2"):
        diffs = [abs(a - b) for a, b in zip(out[name]["losses"], base)]
        ok = max(diffs) <= tol
        print(f"[mesh] {name} vs one_device: max |loss diff| "
              f"{max(diffs):.2e} (tol {tol:g}) {'ok' if ok else 'FAIL'}",
              flush=True)
        if not ok:
            raise SystemExit(f"[mesh] {name} losses diverge from the "
                             f"one-device run: {diffs}")
    if not rehearse:
        peaks = out["one_device"]["peak_bytes"]
        print(f"[mesh] state was spread over all four devices in both "
              f"meshes; the one-device run then raised device 0 alone to "
              f"{peaks[0] / 1e9:.2f} GB", flush=True)
        _kernels_held("mesh", rehearse, {"jit__step": [
            "flash_fwd", "flash_bwd_dkv", "rmsnorm_fwd"]})
    return {"device": dev}


PHASES = {"kernels": phase_kernels, "train": phase_train,
          "serve": phase_serve, "mesh": phase_mesh}


# --------------------------------------------------------------------------- #
# parent: runs the phases as children, never touches jax
# --------------------------------------------------------------------------- #


def _parent_is_clean() -> bool:
    return "jax" not in sys.modules


def run_phases(phases, rehearse: bool) -> dict:
    """Run each phase as a child, one after another; returns the device the
    children reported. Raises SystemExit on the first failure — there is no
    going on after a failed phase."""
    if not _parent_is_clean():
        raise SystemExit("the smoke's parent must stay off jax: a parent "
                         "that holds the chip starves its children")
    env = dict(os.environ)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    device = None
    for name in phases:
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", name]
        if rehearse:
            cmd.append("--rehearse")
        print(f"=== phase {name} ===", flush=True)
        t0 = time.perf_counter()
        proc = subprocess.Popen(cmd, cwd=HERE, env=env,
                                stdout=subprocess.PIPE, text=True)
        result = None
        try:
            deadline = time.monotonic() + PHASE_TIMEOUT_S
            for line in proc.stdout:
                if line.startswith(RESULT_TAG):
                    result = json.loads(line[len(RESULT_TAG):])
                else:
                    sys.stdout.write(line)
                    sys.stdout.flush()
                if time.monotonic() > deadline:
                    raise SystemExit(f"phase {name} exceeded "
                                     f"{PHASE_TIMEOUT_S} s")
            rc = proc.wait(timeout=60)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if rc != 0 or not result or not result.get("ok"):
            raise SystemExit(f"phase {name} FAILED (exit {rc})")
        print(f"=== phase {name} passed in {time.perf_counter() - t0:.1f} s "
              f"===", flush=True)
        if device is not None and result["device"] != device:
            raise SystemExit(f"phase {name} saw device {result['device']}, "
                             f"earlier phases {device}")
        device = result["device"]
    return device


def final_line(device: dict, rehearse: bool) -> str:
    """The contract's last line. A rehearsal only ever names the CPU."""
    if rehearse and device["platform"] != "cpu":
        raise SystemExit("a rehearsal cannot vouch for an accelerator")
    if not rehearse and device["platform"] != "tpu":
        raise SystemExit(f"no TPU: platform {device['platform']!r}")
    return json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run ONLY the four-chip parallel-training path and "
                         "its one-device comparison (needs four chips)")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU control-flow rehearsal at toy size; never "
                         "vouches for a chip")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.rehearse:
        print("REHEARSAL: toy model, CPU pinned — this run says nothing "
              "about the chip", flush=True)
    if args.phase:  # child
        if args.rehearse and os.environ.get("JAX_PLATFORMS") != "cpu":
            raise SystemExit("--rehearse children run under JAX_PLATFORMS=cpu")
        out = PHASES[args.phase](args.rehearse)
        print(RESULT_TAG + json.dumps({"ok": True, **out}), flush=True)
        return 0
    phases = FOUR_CHIP_PHASES if args.four_chip else ONE_CHIP_PHASES
    device = run_phases(phases, args.rehearse)
    want = 4 if args.four_chip else 1
    if not args.rehearse and device["count"] != want:
        raise SystemExit(f"expected {want} device(s), found "
                         f"{device['count']}")
    print(final_line(device, args.rehearse), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
