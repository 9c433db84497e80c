"""Training entry point: ``python -m picotron_tpu.train --config exp.json``.

The TPU single-controller collapse of the reference's ``train.py`` (:57-281).
What torchrun + rendezvous + per-rank env vars did there is one process here:
the config names a (dp, pp, cp, tp) topology, the mesh is built over the
visible devices, and one jitted shard_map program runs the whole 4D step.

Per-step log line carries the same fields the reference prints
(train.py:247-259): step, loss, global batch size, tokens/s, tokens/s/chip,
trained tokens, MFU, device memory — which is exactly what the
extract_metrics CLI scrapes (extract_metrics.py:55-68). wandb logging is
opt-in with the same run-name convention (train.py:132-150); a jax.profiler
trace window replaces the reference's absent profiler (SURVEY.md §5.1).

Fault tolerance (picotron_tpu/resilience/, docs/RESILIENCE.md) is wired
through the loop: SIGTERM/SIGINT finish the in-flight dispatch, flush an
emergency checkpoint, and exit ``EXIT_PREEMPTED``; ANY crash still flushes a
final save via try/finally; re-running the same command auto-resumes from
the latest checkpoint; per-step losses feed an EMA anomaly detector with
skip/rollback/abort policies; and a config-driven chaos injector gives all
of it a deterministic test surface (``make chaos-smoke``).

Telemetry (picotron_tpu/obs, docs/OBSERVABILITY.md): the controller
process writes a per-step metrics JSONL (``$PICOTRON_METRICS_JSONL`` /
``obs.metrics_jsonl``) that ``tools/extract_metrics.py`` ingests instead
of regex-scraping the log; every dispatch records data/dispatch/host-sync
spans (plus checkpoint and consensus-tick spans) into the process trace
ring, dumped as Chrome-trace JSON at exit when ``obs.trace_path`` is set;
rollbacks, anomalies, consensus adoptions, and emergency saves count in
the metrics registry, whose snapshot lands as the JSONL's final summary
row. ``kill -USR2 <pid>`` grabs a timed ``jax.profiler`` capture into
``obs.profile_dir`` without restarting the run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional


def _ensure_devices(cfg) -> None:
    """use_cpu runs (the reference's Gloo path, train.py:83) need the virtual
    CPU device count pinned before a backend exists. On a CPU pod (the
    supervisor's --num-procs exports the rendezvous env) the world is split
    across processes: each rank hosts world/nproc of the virtual devices,
    or the global mesh would see nproc * world."""
    if cfg.distributed.use_cpu:
        n_local = cfg.world_size
        nproc = int(os.environ.get("JAX_NUM_PROCESSES", "1") or 1)
        if os.environ.get("JAX_COORDINATOR_ADDRESS") and nproc > 1:
            if cfg.world_size % nproc:
                raise ValueError(
                    f"world_size {cfg.world_size} is not divisible by the "
                    f"pod's JAX_NUM_PROCESSES={nproc}")
            n_local = cfg.world_size // nproc
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n_local} "
            + os.environ.get("XLA_FLAGS", "")
        )
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax

        jax.config.update("jax_platforms", "cpu")


def _maybe_init_distributed() -> None:
    """Join a multi-host mesh when launched by the pod/slurm template
    (template/base_job.slurm exports these; the analogue of torchrun's
    RANK/WORLD_SIZE rendezvous, reference train.py:83-94). One JAX process
    per host; after initialize(), jax.devices() spans every host's chips."""
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    if not addr:
        return
    import jax

    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        # CPU pods (the reference's Gloo path): without this, any program
        # spanning processes fails with "Multiprocess computations aren't
        # implemented on the CPU backend"
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=addr,
        num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
        process_id=int(os.environ["JAX_PROCESS_ID"]),
    )


def _wandb_init(cfg):
    """Run name convention from the reference: {name}_{tokens-per-step}_
    {topology} (train.py:132-143)."""
    import wandb

    from picotron_tpu.utils import to_readable_format

    d = cfg.distributed
    run_name = (
        f"{cfg.logging.run_name}_{to_readable_format(cfg.tokens_per_step)}"
        f"_dp{d.dp_size}_tp{d.tp_size}_pp{d.pp_size}_cp{d.cp_size}"
    )
    wandb.init(name=run_name, config=cfg.to_dict())
    return wandb


def _touch(path: str) -> None:
    """Heartbeat for the supervisor's stall detector: mtime = liveness."""
    try:
        with open(path, "a"):
            os.utime(path, None)
    except OSError:
        pass  # a lost heartbeat must never kill the training step


def _savable(*trees) -> bool:
    """Whether every leaf is a live array: after a crash INSIDE a donating
    dispatch, the loop variables still reference the donated (deleted)
    inputs, which cannot be saved — the last periodic checkpoint stands."""
    import jax

    return not any(
        getattr(x, "is_deleted", lambda: False)()
        for t in trees for x in jax.tree.leaves(t))


def train(cfg, max_steps_override: Optional[int] = None,
          loss_history: Optional[list] = None):
    """Run the training loop; returns (final_step, trained_tokens, last_loss).

    ``loss_history``, when given, collects ``(step, loss)`` per optimizer
    step — the chaos/equivalence suite compares full trajectories through
    it instead of scraping logs."""
    import jax

    from picotron_tpu import checkpoint as ckpt_mod
    from picotron_tpu import resilience
    from picotron_tpu import train_step as ts
    from picotron_tpu import utils
    from picotron_tpu.data import MicroBatchDataLoader
    from picotron_tpu.models import llama
    from picotron_tpu.obs import (
        GLOBAL_REGISTRY,
        MetricsJsonl,
        Obs,
        ProfileCapture,
    )
    from picotron_tpu.obs.jsonl import resolve_path as jsonl_path
    from picotron_tpu.resilience.anomaly import AnomalyAbort, LossAnomalyDetector
    from picotron_tpu.resilience.chaos import ChaosInjector
    from picotron_tpu.resilience.cluster import ClusterCoordinator, ClusterMonitor
    from picotron_tpu.resilience.preemption import PreemptionGuard
    from picotron_tpu.topology import topology_from_config

    t0_setup = time.perf_counter()
    topo = topology_from_config(cfg)
    m, t, c, lg, r = (cfg.model, cfg.training, cfg.checkpoint, cfg.logging,
                      cfg.resilience)
    utils.set_all_seed(t.seed)

    guard = PreemptionGuard().install() if r.handle_signals \
        else PreemptionGuard()  # not installed: .triggered stays False
    # Pod control plane (resilience/cluster.py): consensus turns ANY host's
    # SIGTERM into the same coordinated break on every host; the monitor is
    # the wedge escape when a host dies outright. Both are inert on a
    # single process.
    coord = (ClusterCoordinator(r.consensus_interval)
             if r.consensus_interval > 0 else None)
    monitor = None
    if r.peer_timeout_s > 0 and jax.process_count() > 1:
        cluster_dir = r.cluster_dir or (
            os.path.join(c.save_dir, "_cluster") if c.save_dir else "")
        if cluster_dir:
            monitor = ClusterMonitor(
                cluster_dir, jax.process_index(), jax.process_count(),
                peer_timeout_s=r.peer_timeout_s,
                lease_interval_s=r.lease_interval_s).start()
        else:
            utils.log0("cluster monitor disabled: peer_timeout_s set but "
                       "no cluster_dir and no checkpoint.save_dir to "
                       "derive one from")
    chaos = ChaosInjector(r, save_dir=c.save_dir)
    detector = LossAnomalyDetector(
        ema_beta=r.anomaly_ema_beta, zscore=r.anomaly_zscore,
        warmup_steps=r.anomaly_warmup_steps)
    # The supervisor's export wins over a static config path: it names the
    # exact file its stall detector watches (PER-RANK in pod mode —
    # <hb>.p<i>); a config path carried over from single-host use would
    # leave the watched files untouched and stall-kill a healthy pod.
    heartbeat = os.environ.get("PICOTRON_HEARTBEAT", "") or r.heartbeat_path
    # Telemetry (docs/OBSERVABILITY.md): per-run registry + the process
    # span ring; the per-step metrics JSONL replaces log-scraping
    # (controller process only — same gating as the log/wandb reports).
    obs = Obs.from_config(cfg.obs)
    jpath = jsonl_path(cfg.obs)
    jsonl = (MetricsJsonl(jpath, log=utils.log0)
             if jpath and utils.is_main_process() else None)
    rollbacks_ctr = obs.registry.counter(
        "picotron_rollbacks_total", "anomaly rollbacks taken")
    adoptions_ctr = obs.registry.counter(
        "picotron_consensus_adoptions_total",
        "peer preemption verdicts adopted via consensus")
    # what the layer stack's attention is, of the configuration alone (the
    # training side of the engine's picotron_kv_pack_factor)
    obs.registry.gauge(
        "picotron_flash_heads_per_row",
        "heads the training flash kernels take to one 128-lane row").set(
            llama.flash_heads_per_row(cfg))

    # state the finally below may touch — defined before anything can raise
    manager = None
    wandb = None
    params = opt_state = None
    step = last_saved_step = trained_tokens = 0
    loss = float("nan")
    profiling = profile_done = False
    # the logging.profile_start/stop window, through the process's one
    # profiler control (obs/profiler.py); a SIGUSR2 capture that happens
    # to be open when the window starts wins, and the window is skipped
    window = ProfileCapture(lg.profile_dir, log=utils.log0,
                            tracer=obs.tracer)
    layout = (m.num_hidden_layers, cfg.distributed.pp_size,
              cfg.distributed.pp_interleave)
    z1 = (cfg.distributed.zero1, cfg.distributed.dp_size)

    try:
        loader = MicroBatchDataLoader(cfg)
        params, opt_state = ts.init_state(cfg, topo)
        if c.hf_bootstrap_path:
            # header-only names+shapes check — zero tensor bytes read; guards
            # BOTH modes against a template that disagrees with the model config
            ckpt_mod.validate_hf_template(c.hf_bootstrap_path, m)
            if c.hf_bootstrap_reinit:
                # reference semantics (checkpoint.py:99-100): the HF file is a
                # shape template only; training starts from the seed-derived
                # random init above
                utils.log0(f"hf_bootstrap_reinit: validated "
                           f"{c.hf_bootstrap_path} as a shape template; keeping "
                           f"random init (reference re-randomize semantics)")
            else:
                params = ckpt_mod.load_hf_safetensors(
                    c.hf_bootstrap_path, m, topo,
                    interleave=cfg.distributed.pp_interleave,
                    fsdp=cfg.distributed.fsdp)
        spc = t.steps_per_call
        step_fn = ts.build_train_step(cfg, topo, multi_step=spc)
        step_fn_single = step_fn if spc == 1 else None  # lazily built for the tail
        step_fn_poison = None  # lazily built chaos NaN-injection program

        # Resume resolution: an explicit load_path is REQUIRED to hold a
        # checkpoint; "auto" (or, with resilience.auto_resume, an empty
        # load_path while save_frequency > 0) discovers the latest checkpoint
        # under save_dir when one exists — re-running the same command
        # continues the run instead of restarting it from scratch.
        resume_dir, resume_required = None, False
        if c.load_path and c.load_path != "auto":
            resume_dir, resume_required = c.load_path, True
        elif c.load_path == "auto" or (r.auto_resume and c.save_frequency > 0):
            resume_dir = c.save_dir
        if c.save_frequency > 0 or resume_dir:
            manager = ckpt_mod.CheckpointManager(
                resume_dir or c.save_dir, io_attempts=r.io_attempts,
                io_backoff=r.io_backoff, io_jitter=r.io_jitter,
                mirror_dir=r.ckpt_mirror_dir)
        if manager is not None and resume_dir and (
                resume_required or manager.latest_step() is not None):
            params, opt_state, step, trained_tokens = manager.load(
                params, opt_state, layout=layout, zero1=z1)
            # geometry guard BEFORE skipping: a changed batch geometry would
            # silently position the loader on different data
            loader.verify_resume(
                (manager.last_restored_meta or {}).get("data"), step)
            loader.skip_steps(step)
            last_saved_step = step
            utils.log0(f"resumed from {resume_dir} at step {step} "
                       f"({utils.to_readable_format(trained_tokens)} tokens)")
            if resume_dir != c.save_dir and c.save_frequency > 0:
                manager.close()
                manager = ckpt_mod.CheckpointManager(
                    c.save_dir, io_attempts=r.io_attempts,
                    io_backoff=r.io_backoff, io_jitter=r.io_jitter,
                    mirror_dir=r.ckpt_mirror_dir)

        # wandb/log gating: only the controller process reports (reference
        # train.py:101, utils.py:12-20)
        wandb = _wandb_init(cfg) if (lg.use_wandb and utils.is_main_process()) else None
        n_params = llama.num_params(m)
        peak = utils.peak_flops_per_chip()
        n_chips = topo.world_size
        max_steps = max_steps_override or t.total_train_steps
        utils.log0(f"model {m.name}: {utils.to_readable_format(n_params)} params | "
              f"mesh dp={topo.dp_size} pp={topo.pp_size} cp={topo.cp_size} "
              f"tp={topo.tp_size} on {n_chips} x {jax.devices()[0].device_kind} | "
              f"global batch {cfg.global_batch_size} "
              f"({utils.to_readable_format(cfg.tokens_per_step)} tokens/step) | "
              f"setup {time.perf_counter() - t0_setup:.1f}s")

        rollbacks = 0
        obs.tracer.claim_loop_thread()  # its scoped spans are "pt:" in a capture
        while step < max_steps and (t.max_tokens is None or trained_tokens < t.max_tokens):
            # Preemption check. With consensus on, the decision is collective:
            # every process all-reduces its local flag at the same boundaries,
            # so a SIGTERM delivered to ONE host becomes the same break — and
            # the same collective emergency save — on ALL hosts. A locally-
            # set flag between rounds waits for the next round; breaking
            # alone would tear the collective save.
            if coord is not None:
                with obs.tracer.span("consensus_tick", step=step):
                    preempt = coord.preempt_now(step, guard.triggered)
            else:
                preempt = guard.triggered
            if preempt:
                if not guard.triggered:
                    # a peer's signal, learned via consensus: adopt it so the
                    # emergency-save path and the exit code behave exactly
                    # like a locally-signaled host (this host's OWN copy of
                    # the pod-wide SIGTERM stays benign, not an escalation)
                    adoptions_ctr.inc()
                    guard.adopt()
                utils.log0(f"preemption: {guard.signame} received; flushing "
                           f"checkpoint at step {step} and exiting "
                           f"{resilience.EXIT_PREEMPTED}", flush=True)
                break
            if heartbeat:
                _touch(heartbeat)
            # Profiler window snaps to dispatch boundaries (a dispatch is spc
            # steps): stop is checked before start so a window narrower than one
            # dispatch still traces one full dispatch; the done latch makes the
            # window fire exactly once.
            if profiling and lg.profile_stop and step >= lg.profile_stop:
                window.stop()
                profiling, profile_done = False, True
            if (lg.profile_start and not profiling and not profile_done
                    and step >= lg.profile_start):
                profiling = window.start()["ok"]
                profile_done = not profiling
            t_start = time.perf_counter()
            step_before = step
            # spc optimizer steps per device dispatch; a tail shorter than spc
            # (by step count OR token budget) would trigger a recompile at a new
            # stack shape — run those steps singly instead.
            steps_left = max_steps - step
            if t.max_tokens is not None:
                tokens_left = t.max_tokens - trained_tokens
                steps_left = min(steps_left, -(-tokens_left // cfg.tokens_per_step))
            k = spc if steps_left >= spc else 1
            poisoned = chaos.poison_step(step + 1)  # config pins spc==1 here
            # per-dispatch spans: data (batch build) -> dispatch (async
            # submit) -> host_sync (blocked on device losses), parented
            # under one train/dispatch root — the serving trace's exact
            # counterpart, dumped at exit via obs.trace_path. Scoped, so a
            # profiler capture of the run names its device gaps by them
            with obs.tracer.span("train/dispatch", step=step_before + 1,
                                 steps=k) as droot:
                if k > 1:
                    fn = step_fn
                elif poisoned:
                    if step_fn_poison is None:
                        step_fn_poison = ts.build_train_step(
                            cfg, topo, poison_nonfinite=True)
                    fn = step_fn_poison
                else:
                    if step_fn_single is None:
                        step_fn_single = ts.build_train_step(cfg, topo)
                    fn = step_fn_single
                with obs.tracer.span("data", parent=droot):
                    if k > 1:
                        tokens, targets = ts.shard_batch_stack(
                            [next(loader) for _ in range(k)], topo)
                    else:
                        tokens, targets = ts.shard_batch(next(loader), topo)
                with obs.tracer.span("dispatch", parent=droot):
                    params, opt_state, loss_arr = fn(
                        params, opt_state, tokens, targets)
                with obs.tracer.span("host_sync", parent=droot):
                    host = utils.host_values(loss_arr)
                    losses = ([float(x) for x in host] if k > 1
                              else [float(host)])
            dt_call = time.perf_counter() - t_start
            obs.registry.histogram(
                "picotron_train_dispatch_seconds",
                "train dispatch wall time (k fused steps)").observe(dt_call)

            # Throughput is per dispatch (identical for every step in the group);
            # mfu/memory are computed lazily, once, and only if a step logs.
            tok_s = k * cfg.tokens_per_step / dt_call
            tok_s_chip = tok_s / n_chips
            stats = None
            do_rollback = False
            for i, loss in enumerate(losses):
                step += 1
                trained_tokens += cfg.tokens_per_step
                if loss_history is not None:
                    loss_history.append((step, loss))
                anom = detector.observe(step, loss)
                if anom is not None:
                    obs.registry.counter(
                        "picotron_loss_anomalies_total",
                        "loss anomalies flagged, by kind",
                        kind=anom.kind).inc()
                    utils.log0(
                        f"loss anomaly at step {step}: loss={loss:.6g} "
                        f"kind={anom.kind} consecutive={anom.consecutive} "
                        f"policy={r.anomaly_policy}", flush=True)
                    if r.anomaly_policy == "abort":
                        raise AnomalyAbort(
                            f"anomalous loss {loss} at step {step} "
                            f"(kind={anom.kind}); anomaly_policy='abort'")
                    if (r.anomaly_policy == "rollback"
                            and anom.consecutive >= r.rollback_after):
                        do_rollback = True
                if step % lg.log_frequency == 0 and stats is None:
                    stats = (utils.get_mfu(tok_s_chip, n_params, m.num_hidden_layers,
                                           m.hidden_size, t.seq_length, peak),
                             utils.device_memory_gb())
                mfu, mem = stats if stats is not None else (None, None)
                if step % lg.log_frequency == 0:
                    parts = [
                        f"Step: {step:<5d}",
                        f"Loss: {loss:6.4f}",
                        f"Global batch size: {utils.to_readable_format(cfg.tokens_per_step)}",
                        f"Tokens/s: {utils.to_readable_format(tok_s)}",
                        f"Tokens/s/chip: {utils.to_readable_format(tok_s_chip)}",
                        f"Tokens: {utils.to_readable_format(trained_tokens)}",
                    ]
                    if mfu is not None:
                        parts.append(f"MFU: {mfu:.2f}%")
                    if mem is not None:
                        parts.append(f"Memory usage: {mem:.2f}GB")
                    utils.log0(" | ".join(parts), flush=True)
                if wandb is not None and step % lg.log_frequency == 0:
                    wandb.log({"loss": loss, "tokens_per_sec": tok_s,
                               "tokens_per_sec_per_chip": tok_s_chip,
                               "trained_tokens": trained_tokens,
                               **({"mfu": mfu} if mfu is not None else {}),
                               **({"memory_gb": mem} if mem is not None else {})},
                              step=step)
                if jsonl is not None:
                    # EVERY step, not just log-frequency ones: the JSONL
                    # is the machine surface, the log line the human one.
                    # mfu/memory stay null off log steps (they are only
                    # computed there); extract_metrics averages over the
                    # non-null rows exactly as it did for the regex path.
                    jsonl.write({
                        "step": step, "loss": loss,
                        "tokens_per_sec": tok_s,
                        "tokens_per_sec_per_chip": tok_s_chip,
                        "trained_tokens": trained_tokens,
                        "mfu_pct": mfu, "memory_gb": mem,
                        "t": round(time.time(), 3)})

            # Save at group boundaries only: params here are the end-of-group
            # state, so the recorded step must be the end-of-group step.
            # A pending rollback skips the save — these params are the
            # anomalous state the rollback exists to discard; saving them
            # first would make the restore below reload the bad step and
            # replay the anomaly until max_rollbacks aborts the run.
            if (manager is not None and c.save_frequency > 0
                    and not do_rollback
                    and step // c.save_frequency > step_before // c.save_frequency):
                with obs.tracer.span("checkpoint", step=step):
                    manager.save(step, params, opt_state, trained_tokens,
                                 layout=layout, zero1=z1,
                                 data_meta=loader.state_meta(step))
                last_saved_step = step

            if monitor is not None:
                monitor.notify_step(step)
            chaos.after_step(step, manager=manager)

            if do_rollback:
                if manager is None or manager.latest_step() is None:
                    raise AnomalyAbort(
                        f"rollback requested at step {step} but no "
                        f"checkpoint exists under {c.save_dir}")
                rollbacks += 1
                rollbacks_ctr.inc()
                if rollbacks > r.max_rollbacks:
                    raise AnomalyAbort(
                        f"anomaly persisted through {r.max_rollbacks} "
                        f"rollbacks; aborting at step {step}")
                with obs.tracer.span("rollback", step=step):
                    params, opt_state, step, trained_tokens = manager.load(
                        params, opt_state, layout=layout, zero1=z1)
                loader.seek_steps(step)
                detector.reset()
                last_saved_step = step
                utils.log0(f"anomaly rollback #{rollbacks}: restored step "
                           f"{step}, replaying", flush=True)
    finally:
        if profiling:
            window.stop()
        obs.tracer.release_loop_thread()
        guard.uninstall()
        flush_abandoned = False
        try:
            # the emergency/final flush: reached on clean completion,
            # preemption, AND any crash — a run never loses more than the
            # current dispatch (unless that dispatch consumed the donated
            # state, in which case the last periodic checkpoint stands)
            if (manager is not None and c.save_frequency > 0 and r.save_on_exit
                    and step > last_saved_step and _savable(params, opt_state)):
                def _flush():
                    manager.save(step, params, opt_state, trained_tokens,
                                 layout=layout, zero1=z1,
                                 data_meta=loader.state_meta(step))

                if guard.triggered:
                    # preemption path: the flush runs on a background
                    # thread, joined with a deadline — a wedged save costs
                    # at most emergency_save_timeout_s of the grace window
                    if guard.emergency_save(
                            _flush, timeout_s=r.emergency_save_timeout_s):
                        utils.log0(f"flushed emergency checkpoint at step "
                                   f"{step}", flush=True)
                    else:
                        flush_abandoned = True
                else:
                    _flush()
                    utils.log0(f"flushed checkpoint at step {step}",
                               flush=True)
        finally:
            if manager is not None and not flush_abandoned:
                try:
                    manager.close()  # drains any in-flight async save
                except Exception as e:
                    utils.log0(f"checkpoint manager close failed: {e!r}")
            if monitor is not None:
                # Stopped only AFTER the final (collective) flush: a peer
                # dying mid-save still needs the wedge escape. Mark done
                # only on clean/coordinated exits — a crash's stale lease
                # is exactly how the peers learn this host is gone.
                monitor.stop(mark_done=sys.exc_info()[0] is None)
            if wandb is not None:
                wandb.finish()
            if jsonl is not None:
                # the run's registry snapshot (rollbacks, anomalies,
                # adoptions, retries, emergency saves, dispatch timing)
                # rides out as the terminal summary row — consumers key
                # rows on "step" and skip it
                jsonl.write({"event": "summary",
                             "metrics": {**obs.registry.summary(),
                                         **GLOBAL_REGISTRY.summary()}})
                jsonl.close()
            if cfg.obs.trace_path and utils.is_main_process() \
                    and obs.enabled:
                try:
                    obs.tracer.dump_chrome(cfg.obs.trace_path)
                except OSError as e:
                    utils.log0(f"trace dump failed: {e!r}")
    return step, trained_tokens, loss


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="picotron-tpu trainer (one JSON config per experiment, "
                    "reference train.py:57-63)")
    parser.add_argument("--config", required=True, help="path to config.json")
    parser.add_argument("--max-steps", type=int, default=None,
                        help="override training.total_train_steps")
    args = parser.parse_args(argv)

    with open(args.config) as f:
        raw = json.load(f)
    from picotron_tpu.config import Config
    from picotron_tpu.utils import log0

    cfg = Config.from_dict(raw)
    _ensure_devices(cfg)
    _maybe_init_distributed()
    from picotron_tpu.utils import enable_compile_cache

    enable_compile_cache()  # before the first compile
    if cfg.obs.enabled:
        # kill -USR2 <pid> -> one timed jax.profiler capture into
        # obs.profile_dir: the "this run is slow RIGHT NOW" surface,
        # no restart or pre-planned profile window needed
        from picotron_tpu.obs import ProfileCapture, install_sigusr2

        install_sigusr2(ProfileCapture(
            cfg.obs.profile_dir, cfg.obs.profile_seconds, log=log0))
    from picotron_tpu import resilience
    from picotron_tpu.resilience.anomaly import AnomalyAbort

    try:
        step, tokens, loss = train(cfg, max_steps_override=args.max_steps)
    except AnomalyAbort as e:
        log0(f"aborted by anomaly policy: {e}")
        return resilience.EXIT_ANOMALY
    if resilience.was_preempted():
        log0(f"preempted; checkpoint flushed at step {step} — exit "
             f"{resilience.EXIT_PREEMPTED} (re-run the same command to resume)")
        return resilience.EXIT_PREEMPTED
    log0(f"done: {step} steps, {tokens} tokens, final loss {loss:.4f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
