"""Benchmark: SmolLM-1.7B training MFU on the available chip(s).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", "device"}.
Baseline is the reference's headline SmolLM-1.7B number: ~50% MFU on 8xH100
(reference README.md:7); vs_baseline = our_mfu / 50.

Protocol mirrors the reference's extract_metrics.py:82-89: time real optimizer
steps, skip the first 3 as warmup, mean the rest. MFU uses the reference's
analytic formula (utils.py:42-48) with the per-chip peak-FLOPs table in
picotron_tpu.utils instead of the hardcoded H100 constant.

The measurement runs in THIS process, on the accelerator JAX finds, at the
full model, and fails where it stands: no child process, no smaller model,
no CPU ladder. Without a chip the script refuses to measure unless the
caller pinned ``JAX_PLATFORMS=cpu`` (then the record names the CPU and
carries no MFU). On-chip kernel parity lives in ``chip_smoke.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax

from picotron_tpu.bench_record import BENCH_METRICS
from picotron_tpu.obs.metrics import MetricsRegistry

# the last COMPLETED run's registry summary (picotron_tpu/obs): run()
# times each call into a FRESH registry and publishes the snapshot here
# only when the run finishes, so the final JSON's "obs" blob describes
# exactly the run whose number it reports — OOM'd/descended sizes and a
# losing flash-layout A/B leg never pollute it
LAST_RUN_OBS: dict = {}


def smollm_cfg(mbs: int, seq: int, remat: str = "full"):
    from picotron_tpu.config import SMOLLM_1_7B, Config

    return Config.from_dict({
        "distributed": {"dp_size": 1, "pp_size": 1, "cp_size": 1, "tp_size": 1},
        "model": dict(SMOLLM_1_7B),
        "training": {"seq_length": seq, "micro_batch_size": mbs,
                     "gradient_accumulation_steps": 1, "remat": remat,
                     "grad_accum_dtype": "param", "learning_rate": 3e-4},
        "dataset": {"name": "synthetic"},
    })


def run(cfg, calls=4, warmup=1, steps_per_call=16):
    """Time multi-step calls (K optimizer steps fused into one dispatch via
    lax.scan — an on-device training loop, so per-step host latency doesn't
    pollute the measurement); first `warmup` calls (compile + cache) skipped."""
    from picotron_tpu import train_step as ts
    from picotron_tpu.data import MicroBatchDataLoader
    from picotron_tpu.topology import topology_from_config

    topo = topology_from_config(cfg, devices=jax.devices()[:1])
    params, opt_state = ts.init_state(cfg, topo)
    step = ts.build_train_step(cfg, topo, multi_step=steps_per_call)
    loader = MicroBatchDataLoader(cfg)
    tokens, targets = ts.shard_batch_stack(
        [next(loader) for _ in range(steps_per_call)], topo)

    times = []
    reg = MetricsRegistry()
    call_hist = reg.histogram(
        "bench_step_call_seconds",
        f"one timed call ({steps_per_call} fused optimizer steps)")
    for _ in range(calls):
        t0 = time.perf_counter()
        params, opt_state, losses = step(params, opt_state, tokens, targets)
        jax.block_until_ready(losses)
        times.append(time.perf_counter() - t0)
        call_hist.observe(times[-1])
    assert jax.numpy.isfinite(losses).all(), f"loss diverged: {losses}"
    mean_t = sum(times[warmup:]) / len(times[warmup:])
    # publish only on completion — an aborted run's partial timings die
    # with its local registry
    LAST_RUN_OBS.clear()
    LAST_RUN_OBS.update(reg.summary())
    return steps_per_call * cfg.tokens_per_step / mean_t


class EntryTimeout(Exception):
    """A single ladder entry (compile + timed runs) exceeded its watchdog."""


# exit code of a ladder that gave up on a wedged entry (EX_TEMPFAIL from
# sysexits): distinct from rc=1, a failure of the bench code itself
EX_INFRA = 75


class _entry_watchdog:
    """SIGALRM deadline around one ladder entry, so one entry that never
    returns costs its own cap and not the whole run. Main thread only;
    seconds <= 0 disables."""

    def __init__(self, seconds: float):
        self.seconds = seconds

    def __enter__(self):
        import signal

        if self.seconds <= 0:
            return self
        def _fire(signum, frame):
            raise EntryTimeout(
                f"ladder entry exceeded its {self.seconds:.0f}s watchdog")
        self._prev = signal.signal(signal.SIGALRM, _fire)
        signal.setitimer(signal.ITIMER_REAL, self.seconds)
        return self

    def __exit__(self, *exc):
        import signal

        if self.seconds <= 0:
            return False
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, self._prev)
        return False


def _entry_timeout_s() -> float:
    """Per-entry watchdog for run_descending, well above a healthy entry's
    compile plus timed runs. Override with PICOTRON_BENCH_ENTRY_TIMEOUT
    (seconds; 0 disables)."""
    try:
        return float(os.environ.get("PICOTRON_BENCH_ENTRY_TIMEOUT", "900"))
    except ValueError:
        return 900.0


def classify_bench_error(msg: str) -> str:
    """'oom' = out of HBM (descend to a smaller size); anything else
    re-raises."""
    if any(s in msg for s in ("resource_exhausted", "out of memory",
                              "exceeds the amount of memory available")):
        return "oom"
    return "raise"


def run_descending(sizes, make_cfg, tag, **run_kw):
    """Try configs from `sizes` in order — callers order them descending by
    memory footprint, best-expected-MFU first among comparable footprints.
    An OOM moves to the next entry, a watchdog trip retries the same entry
    once (a second trip anywhere gives up with EX_INFRA), anything else
    raises. Returns (cfg, tokens_per_sec) of the first entry that runs."""
    import gc

    last_err = None
    trips = 0
    for size in sizes:
        cfg = make_cfg(size)
        for attempt in range(2):
            try:
                with _entry_watchdog(_entry_timeout_s()):
                    return cfg, run(cfg, **run_kw)
            except Exception as e:
                msg = str(e).lower()
                last_err = msg
                if isinstance(e, EntryTimeout):
                    trips += 1
                    if trips >= 2:
                        print(f"# {tag}: {trips} watchdog trips; giving up "
                              f"({msg})", file=sys.stderr)
                        raise SystemExit(EX_INFRA) from None
                elif classify_bench_error(msg) == "raise":
                    raise
                # the exception's traceback pins the failed attempt's
                # device arrays via frame refs; break it explicitly so the
                # collect below can actually free HBM for the next attempt
                oom = not isinstance(e, EntryTimeout)
                e.__traceback__ = None
                del e
                jax.clear_caches()
                gc.collect()
                if oom:
                    print(f"# {tag}: OOM at {size}, trying smaller "
                          f"({msg[:120]})", file=sys.stderr)
                    break
                print(f"# {tag}: watchdog trip at {size}; retrying the "
                      f"same size once", file=sys.stderr)
    raise SystemExit(f"{tag} failed at all sizes: {last_err}")


def try_flash_layout_ab(cfg, tok_s_folded, **run_kw):
    """One extra timed run of the winning config with the transpose-free
    'merged' flash layout, where the geometry allows it (head_dim % 128 ==
    0, e.g. the 7B proxy's D=128); other geometries have no second layout
    the chip's compiler accepts and keep 'folded' without a run. A leg
    that fails fails the bench. Returns (cfg, tokens_per_sec)."""
    import copy
    import gc

    from picotron_tpu.ops.pallas.flash_attention import LANE

    if cfg.model.head_dim % LANE:
        return cfg, tok_s_folded
    cfg2 = copy.deepcopy(cfg)
    cfg2.model.flash_layout = "merged"
    folded_obs = dict(LAST_RUN_OBS)  # the winning folded run's snapshot
    jax.clear_caches()
    gc.collect()
    with _entry_watchdog(_entry_timeout_s()):
        tok_s = run(cfg2, **run_kw)
    if tok_s > tok_s_folded:
        print(f"# flash_layout=merged wins: {tok_s:.0f} vs "
              f"{tok_s_folded:.0f} tok/s "
              f"(+{100 * (tok_s / tok_s_folded - 1):.1f}%)", file=sys.stderr)
        return cfg2, tok_s
    print(f"# flash_layout=merged slower: {tok_s:.0f} vs {tok_s_folded:.0f} "
          f"tok/s; keeping folded", file=sys.stderr)
    # the published number is the folded run's — restore its obs snapshot
    # over the losing alt leg's
    LAST_RUN_OBS.clear()
    LAST_RUN_OBS.update(folded_obs)
    return cfg, tok_s_folded


def main():
    from picotron_tpu.models import llama
    from picotron_tpu.utils import (device_record, enable_compile_cache,
                                    get_mfu, peak_flops_per_chip,
                                    require_accelerator)

    require_accelerator("bench.py")  # no chip, no CPU pin: no measurement
    enable_compile_cache()
    # (remat, mbs) candidates, descending by activation memory (save_attn
    # stores the flash out+LSE on top of layer boundaries, roughly
    # full@2*mbs): the reference trains WITHOUT activation checkpointing,
    # so lighter remat is parity behavior and the saved recompute FLOPs
    # turn into MFU; larger-HBM chips get the larger save_attn batches
    # first. (remat="none" is an HBM wall at this scale: ~14.5 GB static
    # state + 6+ GB of unrematerialized residuals on a 16 GB chip —
    # docs/BENCH_7B.md has the arithmetic; it stays a config option for
    # smaller models / larger chips.)
    sizes = (("save_attn", 8), ("save_attn", 4), ("save_attn", 2),
             ("full", 4), ("save_attn", 1), ("full", 2), ("full", 1))
    cfg, tok_s = run_descending(
        sizes, lambda rm: smollm_cfg(mbs=rm[1], seq=2048, remat=rm[0]),
        tag="bench")
    cfg, tok_s = try_flash_layout_ab(cfg, tok_s)

    m = cfg.model
    n_params = llama.num_params(m)
    peak = peak_flops_per_chip()  # None on a pinned CPU: no MFU to report
    mfu = get_mfu(tok_s, n_params, m.num_hidden_layers, m.hidden_size,
                  cfg.training.seq_length, peak)
    print(json.dumps({"metric": BENCH_METRICS["bench"],
                      "value": None if mfu is None else round(mfu, 2),
                      "unit": "%",
                      "vs_baseline": None if mfu is None
                      else round(mfu / 50.0, 3),
                      "tokens_per_sec_per_chip": round(tok_s, 1),
                      "device": device_record(),
                      "obs": dict(LAST_RUN_OBS)}))
    print(f"# mbs={cfg.training.micro_batch_size} seq={cfg.training.seq_length} "
          f"remat={cfg.training.remat} flash={cfg.model.flash_layout} "
          f"tokens/s/chip={tok_s:.0f} params={n_params/1e9:.2f}B "
          f"peak={'n/a' if peak is None else f'{peak/1e12:.0f}TF'}",
          file=sys.stderr)


if __name__ == "__main__":
    main()
