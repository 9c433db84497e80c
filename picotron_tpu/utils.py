"""Logging, formatting, MFU accounting.

Ports of the reference's picotron/utils.py, TPU-ified: the analytic MFU
formula is kept (utils.py:42-48) but the hardcoded H100 989.5 TFLOPs
denominator becomes a per-chip-generation table; the fcntl-locked multi-process
print (utils.py:12-20) is unnecessary under a single controller.
"""

from __future__ import annotations

import os

import jax
import numpy as np

# dense bf16 peak FLOPs per chip
TPU_PEAK_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,  # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}
H100_PEAK_FLOPS = 989.5e12  # the reference's denominator (utils.py:42)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.
    ``JAX_COMPILATION_CACHE_DIR`` wins when set: JAX reads it itself and
    nothing is set in code. Otherwise the cache lives at ONE fixed path
    inside the checkout — the path is part of the cache key, so a
    directory built from a temp name, a pid or the time would never hit.
    Every entry point calls this before its first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def shard_map(f, mesh, in_specs, out_specs, check_vma: bool = False):
    """``jax.shard_map`` with this repo's default: the varying-manual-axes
    checker off unless ``distributed.check_vma`` asks for it. Every
    shard_map in the repo goes through here."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def typeof_vma(x) -> frozenset:
    """The varying-manual-axes set of ``x``'s type. Every vma-driven cast
    in the repo keys off this."""
    return frozenset(jax.typeof(x).vma)


def is_main_process() -> bool:
    """True on the controller process that should own logging/wandb/metadata
    (the reference gates prints on global rank 0 via an fcntl lock,
    utils.py:12-20, and wandb on wandb_rank, train.py:101). Collective-side
    work (orbax saves, the train step itself) must NOT be gated — every
    process participates there."""
    return jax.process_index() == 0


def log0(*args, **kwargs) -> None:
    """print() on process 0 only — the multi-host log gate."""
    if is_main_process():
        print(*args, **kwargs)


def host_values(x) -> np.ndarray:
    """Fetch a replicated device array to the host, multi-process safe.

    On a multi-controller pod a replicated output (the loss, the consensus
    verdict) spans every host's devices, and jax refuses whole-array reads
    of non-addressable shards — but each host holds a full copy, so the
    first addressable shard IS the value. Single-process arrays take the
    plain path untouched."""
    x = jax.block_until_ready(x)
    if getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    return np.asarray(x.addressable_data(0))


def on_tpu() -> bool:
    """Trace-time backend check gating the Pallas (Mosaic) fast paths: only
    an actual TPU backend qualifies — GPU must not be routed into kernels
    lowered for Mosaic."""
    return jax.default_backend() == "tpu"


def device_record() -> dict:
    """The device a result was taken on, as JAX reports it — the keys
    ``chip_smoke.py``'s last line carries."""
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_flops_per_chip(device=None) -> float | None:
    """Dense bf16 peak of one chip, keyed by ``device_kind``. ``None`` on
    the CPU platform only (tests: MFU is not reported there); an
    accelerator this table does not know is an error, never a silent
    default or a dropped MFU."""
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return None
    kind = getattr(device, "device_kind", "").lower()
    for key, val in TPU_PEAK_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to utils.TPU_PEAK_FLOPS")


def flops_per_token(num_params: int, num_layers: int, hidden: int, seq_len: int) -> float:
    """6N + 12*layers*hidden*seq (reference utils.py:42-48: param FLOPs +
    attention quadratic term)."""
    return 6 * num_params + 12 * num_layers * hidden * seq_len


def get_mfu(tokens_per_sec_per_chip: float, num_params: int, num_layers: int,
            hidden: int, seq_len: int, peak: float | None) -> float | None:
    if peak is None:
        return None
    fpt = flops_per_token(num_params, num_layers, hidden, seq_len)
    return 100.0 * fpt * tokens_per_sec_per_chip / peak


def to_readable_format(num: float, precision: int = 2) -> str:
    """1234567 -> '1.23M' (reference utils.py:27-37)."""
    for bound, suffix in ((1e12, "T"), (1e9, "B"), (1e6, "M"), (1e3, "K")):
        if abs(num) >= bound:
            return f"{num / bound:.{precision}f}{suffix}"
    return f"{num:.{precision}f}"


def set_all_seed(seed: int) -> None:
    np.random.seed(seed)


def device_memory_gb(device=None) -> float | None:
    """Peak live bytes across this process's devices (the reference logs
    torch.cuda.memory_reserved of the local rank, train.py:257). Max, not
    device 0: pp/tp shards can differ in footprint and the max is what OOMs."""
    devices = [device] if device is not None else jax.local_devices()
    best = None
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            continue
        if stats:
            b = stats.get("peak_bytes_in_use",
                          stats.get("bytes_in_use", 0)) / 1e9
            best = b if best is None else max(best, b)
    return best


def pvary_like(x, *refs):
    """Cast every leaf of ``x`` to be VARYING over the union of the
    ``refs``' varying mesh axes, for shard_map's varying-manual-axes
    checker (``check_vma=True``). A pure type cast, numerically the
    identity, and a no-op where the leaf already varies. Needed where a
    replicated literal (a ``jnp.zeros`` scan carry, a masked fill) meets
    axis-varying values: the checker would otherwise reject the scan
    carry as replicated-in/varying-out."""
    import jax
    from jax import lax

    target = frozenset().union(
        *[typeof_vma(r) for r in jax.tree.leaves(refs)])

    def cast(v):
        need = tuple(sorted(target - typeof_vma(v)))
        return lax.pcast(v, need, to="varying") if need else v

    return jax.tree.map(cast, x)


def vma_checking(axis: str) -> bool:
    """Whether shard_map's varying-manual-axes checker is typing the
    current trace: a fresh ``axis_index`` is vma-typed iff it is. Used to
    skip the checker-only eval_shape passes (scan-carry fixpoints) on the
    production (``check_vma=False``) build, where every vma is empty and
    the casts are provable no-ops."""
    from jax import lax

    return bool(typeof_vma(lax.axis_index(axis)))


def scan_carry_fixpoint(body, carry, x_example):
    """Cast a ``lax.scan`` carry to the varying-manual-axes fix-point of
    ``body(carry, x) -> (carry, y)`` under shard_map's ``check_vma``: a
    replicated init meeting axis-varying values inside the body must enter
    the scan already typed with the body's output vma. Numerically the
    identity; converges in a few ``eval_shape`` passes (vma only grows);
    a no-op when the checker is off (every vma is empty). Casting to the
    fix-point (rather than some outer upper bound) matters: over-casting
    leaks spurious varying axes into downstream cotangents."""
    import jax

    # vma growth can propagate between carry leaves one pass at a time, so
    # the cap scales with the carry's size; non-convergence fails HERE with
    # a named error instead of as the checker's opaque
    # replicated-in/varying-out complaint at the scan itself
    for _ in range(max(4, len(jax.tree.leaves(carry)) + 1)):
        out = jax.eval_shape(lambda c: body(c, x_example)[0], carry)
        new = jax.tree.map(pvary_like, carry, out)
        if [typeof_vma(a) for a in jax.tree.leaves(new)] == \
           [typeof_vma(a) for a in jax.tree.leaves(carry)]:
            return new
        carry = new
    raise ValueError(
        "scan_carry_fixpoint did not converge: the scan body keeps adding "
        "varying axes to its carry across passes — check the body for a "
        "vma-oscillating construct")


def collective_scan_unroll():
    """Workaround for an XLA CPU runtime race: InProcessCommunicator's
    rendezvous for collective-permutes inside While loops can admit
    participants from adjacent loop iterations (observed:
    "Check failed: id < num_threads (8 vs. 8) ... collective permute
    RendezvousKey"), aborting the process. Fully unrolling ppermute-bearing
    scans gives every permute a distinct op id, which sidesteps the
    collision. TPU runtimes are unaffected, and the hot loops stay rolled
    there for compile time."""
    import jax

    return True if jax.default_backend() == "cpu" else 1
