"""Bounded-restart watchdog: ``python -m picotron_tpu.tools.supervise [opts] -- cmd...``

The outermost layer of the resilience stack (docs/RESILIENCE.md): keeps a
trainer — one process, or a whole multi-host pod — running across crashes,
preemptions, and dead hosts without ever looping forever.

- **bounded restarts** — a nonzero exit relaunches the command after an
  exponential backoff, at most ``--max-restarts`` times; then the child's
  final exit code is propagated (a scheduler sees the real failure, not a
  lying 0). The budget REPLENISHES: after ``--healthy-reset`` seconds of
  uptime a failure counts from zero again, so a long run that hiccups once
  a day is not killed by arithmetic after a few weeks (0 = legacy
  never-replenish);
- **stall detection** — the child heartbeats a file (the trainer touches
  ``$PICOTRON_HEARTBEAT`` every dispatch); a heartbeat older than
  ``--stall-timeout`` — or MISSING after launch (deleting it must not
  silently disable the detector) — means the run is wedged (deadlocked
  collective, hung remote mount): SIGTERM, a grace period, then SIGKILL,
  counted as a restart;
- **preemption aware** — exit code ``EXIT_PREEMPTED`` (75) means "resumable
  checkpoint written, re-run me"; it is restarted like any failure but the
  trainer's auto-resume makes the relaunch continue the run;
- **serve mode** (``--serve``) — the child is a serving replica
  (``tools/serve.py``): clean drains (exit 0) relaunch WITHOUT charging
  the restart budget (a drain is a rollout, not a crash), nonzero exits
  (serve exits 1 when its dispatch loop dies) walk the normal ladder,
  and the supervisor's own SIGTERM/SIGINT forwards to the child and ends
  supervision with its exit code — one supervisor per fleet member keeps
  an N-replica router fabric (``tools/router.py``) populated;
- **spot-quota aware** — a launch that dies within ``--quota-window``
  seconds never produced a step (no capacity, quota exhausted, a dead
  coordinator): those retry on their own long, capped backoff ladder
  (``--quota-backoff`` doubling up to ``--quota-backoff-max``) WITHOUT
  burning the restart budget, bounded by ``--max-launch-retries``.

**Pod mode** (``--num-procs N``) supervises one multi-controller pod
locally: N copies of the command, each with ``JAX_PROCESS_ID`` /
``JAX_NUM_PROCESSES`` / ``PICOTRON_POD_RANK`` (and a per-rank heartbeat
``<hb>.p<i>``) in its environment. The pod lives and dies together —
that is what keeps collectives coherent:

- every rank exiting 0 ⇒ done;
- any rank exiting 75 (preempted — its peers follow via the consensus in
  resilience/cluster.py) ⇒ the stragglers get ``--term-grace`` to finish
  their own coordinated exit, then the pod restarts as resumable;
- any rank crashing or exiting ``EXIT_CLUSTER_FAILED`` (77: a peer died
  inside a collective) ⇒ terminate the stragglers, restart the pod
  together;
- any rank's heartbeat going stale ⇒ kill and restart the whole pod.

**Per-host pods** (one supervisor per host, e.g. under SLURM) coordinate
through ``--epoch-file`` on shared storage instead: a supervisor whose
child fails bumps the epoch; every supervisor polling a bumped epoch
terminates its own child (SIGTERM — the trainer still takes its emergency
save) and relaunches, so the pod restarts together without a shared
process table. Epoch restarts triggered by a PEER do not consume the
local restart budget — the failing host's supervisor accounts for them.

Typical use::

    python -m picotron_tpu.tools.supervise --max-restarts 5 \
        --heartbeat /tmp/hb --stall-timeout 600 -- \
        python -m picotron_tpu.train --config exp.json

    # a 2-process local pod with coordinated restarts
    python -m picotron_tpu.tools.supervise --num-procs 2 \
        --coordinator localhost:8476 -- \
        python -m picotron_tpu.train --config exp.json
"""

from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Optional

# Mirrors picotron_tpu.resilience.{EXIT_PREEMPTED, EXIT_CLUSTER_FAILED};
# duplicated so the supervisor never imports jax (tests pin the values in
# lockstep).
EXIT_PREEMPTED = 75
EXIT_CLUSTER_FAILED = 77


def _heartbeat_age(path: str, launched_at: float) -> float:
    """Age of the child's liveness signal. ``launched_at`` (wall clock) seeds
    the no-file case: the launch touch creates the file, so a missing file
    afterwards means it was DELETED — counting its age from launch makes
    deletion read as a growing stall instead of silently disabling the
    detector forever (the old behavior returned 0.0 = "perfectly fresh")."""
    try:
        return time.time() - os.path.getmtime(path)
    except OSError:
        return time.time() - launched_at


def _touch(path: str) -> None:
    with open(path, "a"):
        os.utime(path, None)


def _terminate(proc: subprocess.Popen, grace: float) -> int:
    """SIGTERM, wait out the grace period, SIGKILL. Returns the exit code."""
    proc.send_signal(signal.SIGTERM)
    try:
        return proc.wait(timeout=grace)
    except subprocess.TimeoutExpired:
        proc.kill()
        return proc.wait()


def _shell_code(rc: int) -> int:
    """Shell convention for signal deaths: ``rc < 0`` → ``128 - rc``
    (SIGTERM → 143, SIGKILL → 137), so schedulers see the signal."""
    return rc if rc >= 0 else 128 - rc


def _read_epoch(path: str) -> int:
    try:
        with open(path) as f:
            return int(f.read().strip() or 0)
    except (OSError, ValueError):
        return 0


def _bump_epoch(path: str, beyond: int) -> None:
    """Advance the shared restart epoch past ``beyond`` (atomic rename;
    concurrent bumps from several hosts may collapse into one epoch, which
    is fine — one pod restart is exactly what they all asked for)."""
    nxt = max(_read_epoch(path), beyond) + 1
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            f.write(str(nxt))
        os.replace(tmp, path)
    except OSError as e:
        print(f"supervise: failed to bump epoch file {path}: {e}",
              flush=True)


class _RestartBudget:
    """Restart accounting shared by single and pod mode: bounded attempts,
    healthy-uptime replenishment, and the spot-quota launch-failure ladder.
    """

    def __init__(self, max_restarts: int, backoff: float, backoff_max: float,
                 healthy_reset: float = 600.0, quota_window: float = 0.0,
                 quota_backoff: float = 30.0, quota_backoff_max: float = 1800.0,
                 max_launch_retries: int = 120):
        self.max_restarts = max_restarts
        self.backoff = backoff
        self.backoff_max = backoff_max
        self.healthy_reset = healthy_reset
        self.quota_window = quota_window
        self.quota_backoff = quota_backoff
        self.quota_backoff_max = quota_backoff_max
        self.max_launch_retries = max_launch_retries
        self.attempt = 0  # restarts charged to the budget so far
        self.launch_failures = 0  # consecutive quota-style fast failures

    def record(self, uptime: float, preempted: bool = False,
               stalled: bool = False) -> Optional[tuple[str, float]]:
        """Classify one failed run given its uptime; returns ``(kind,
        delay_s)`` for the relaunch or None when the budget is exhausted.
        ``preempted`` runs are never quota failures — they held capacity
        and checkpointed; dying fast is the preemption's fault. ``stalled``
        runs never replenish (their uptime includes >= stall_timeout of
        DEAD time: with stall_timeout >= healthy_reset a permanently
        wedged trainer would otherwise reset the budget every cycle and
        relaunch forever) and never read as quota launch failures (they
        held capacity — they just hung)."""
        if (self.quota_window > 0 and uptime < self.quota_window
                and not preempted and not stalled):
            # never reached a working step: no-capacity/quota-style launch
            # failure — wait long (the pool refills in minutes, not
            # milliseconds), don't charge the crash budget
            self.launch_failures += 1
            if (self.max_launch_retries > 0
                    and self.launch_failures > self.max_launch_retries):
                return None
            delay = min(self.quota_backoff * 2 ** (self.launch_failures - 1),
                        self.quota_backoff_max)
            return (f"launch failure {self.launch_failures}"
                    f"/{self.max_launch_retries or 'inf'}", delay)
        self.launch_failures = 0
        if not stalled and self.healthy_reset > 0 and uptime >= self.healthy_reset:
            # the run was healthy long enough that prior failures are
            # stale history: replenish the budget and restart the ladder
            self.attempt = 0
        self.attempt += 1
        if self.attempt > self.max_restarts:
            return None
        delay = min(self.backoff * 2 ** (self.attempt - 1), self.backoff_max)
        return (f"restart {self.attempt}/{self.max_restarts}", delay)


def run_supervised(cmd, max_restarts: int = 3, backoff: float = 1.0,
                   backoff_max: float = 60.0, heartbeat: str = "",
                   stall_timeout: float = 0.0, term_grace: float = 10.0,
                   poll_interval: float = 0.2, healthy_reset: float = 600.0,
                   quota_window: float = 0.0, quota_backoff: float = 30.0,
                   quota_backoff_max: float = 1800.0,
                   max_launch_retries: int = 120, epoch_file: str = "",
                   metrics_jsonl: str = "", serve_mode: bool = False,
                   sleep=time.sleep) -> int:
    """Run ``cmd`` under supervision; returns the exit code to propagate.
    ``stall_timeout`` <= 0 disables stall detection; ``epoch_file`` joins a
    per-host pod (see the module docstring). Importable so the chaos suite
    drives it in-process (the children are still real subprocesses).

    ``serve_mode`` (the ``--serve`` flag) supervises a serving replica
    (``tools/serve.py``) instead of a trainer, with restart-ALWAYS fleet
    semantics: a clean drain (exit 0) relaunches the replica after
    ``backoff`` WITHOUT charging the restart budget — a drain is an
    intentional event (SIGTERM rollout, a router pulling the replica),
    not a crash — while nonzero exits (a dead dispatch loop exits 1)
    walk the existing budget/backoff ladder. The fleet is stopped
    through the SUPERVISOR: its own SIGTERM/SIGINT is forwarded to the
    child (which drains) and supervision ends with the child's exit
    code. Signal forwarding is installed only on the main thread."""
    env = dict(os.environ)
    if heartbeat:
        env["PICOTRON_HEARTBEAT"] = heartbeat
    if metrics_jsonl:
        # the trainer appends its per-step metrics JSONL here (the
        # structured surface extract_metrics.py prefers over the log
        # regex); append semantics make restarts stitch into one file
        env["PICOTRON_METRICS_JSONL"] = metrics_jsonl
    budget = _RestartBudget(
        max_restarts, backoff, backoff_max, healthy_reset=healthy_reset,
        quota_window=quota_window, quota_backoff=quota_backoff,
        quota_backoff_max=quota_backoff_max,
        max_launch_retries=max_launch_retries)
    # serve mode: the supervisor is the fleet's stop surface — forward
    # SIGTERM/SIGINT to the child (it drains) and end supervision with
    # its exit code. Only installable from the main thread (tests drive
    # this function from worker threads, where the default disposition
    # already applies).
    stop_req = {"flag": False, "proc": None}
    restore: dict = {}
    if serve_mode and threading.current_thread() is threading.main_thread():
        def _forward(signum, frame):
            stop_req["flag"] = True
            p = stop_req["proc"]
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGTERM)

        for s in (signal.SIGTERM, signal.SIGINT):
            restore[s] = signal.signal(s, _forward)
    try:
        return _run_supervised_loop(
            cmd, env, budget, stop_req, max_restarts=max_restarts,
            backoff=backoff, heartbeat=heartbeat,
            stall_timeout=stall_timeout, term_grace=term_grace,
            poll_interval=poll_interval, epoch_file=epoch_file,
            serve_mode=serve_mode, sleep=sleep)
    finally:
        for s, handler in restore.items():
            signal.signal(s, handler)


def _run_supervised_loop(cmd, env, budget, stop_req, *, max_restarts,
                         backoff, heartbeat, stall_timeout, term_grace,
                         poll_interval, epoch_file, serve_mode,
                         sleep) -> int:
    while True:
        if heartbeat:
            _touch(heartbeat)  # launch counts as liveness: startup gets a full window
        launch_epoch = _read_epoch(epoch_file) if epoch_file else 0
        launched_at = time.time()
        t0 = time.monotonic()
        print(f"supervise: launching (restarts used "
              f"{budget.attempt}/{max_restarts}): {' '.join(cmd)}",
              flush=True)
        # one process per chip: this parent imports the package (and so jax) but
        # never initialises a backend, so the chip is free for the child
        proc = subprocess.Popen(cmd, env=env)
        stop_req["proc"] = proc
        if stop_req["flag"] and proc.poll() is None:
            # the stop signal landed between launches: this child never
            # saw the forward — deliver it now
            proc.send_signal(signal.SIGTERM)
        stalled = peer_restart = False
        next_epoch_poll = 0.0  # epoch lives on shared storage: poll it on
        # its own >= 1s cadence, not every child-liveness tick
        while True:
            rc = proc.poll()
            if rc is not None:
                break
            if (heartbeat and stall_timeout > 0
                    and _heartbeat_age(heartbeat, launched_at) > stall_timeout):
                print(f"supervise: heartbeat stale for > {stall_timeout}s; "
                      f"killing the stalled trainer", flush=True)
                rc = _terminate(proc, term_grace)
                stalled = True
                break
            if epoch_file and time.monotonic() >= next_epoch_poll:
                next_epoch_poll = time.monotonic() + max(poll_interval, 1.0)
                if _read_epoch(epoch_file) > launch_epoch:
                    print("supervise: pod restart epoch bumped by a peer "
                          "host; terminating for a coordinated relaunch",
                          flush=True)
                    rc = _terminate(proc, term_grace)
                    peer_restart = True
                    break
            sleep(poll_interval)
        if stop_req["flag"]:
            # operator stop: the forwarded SIGTERM drained the child —
            # propagate its verdict (0 on a clean drain), never relaunch
            code = _shell_code(rc)
            print(f"supervise: stop requested; child exited {code}",
                  flush=True)
            return code
        if rc == 0 and not stalled and not peer_restart:
            if serve_mode:
                # a replica drain is intentional, not a crash: keep the
                # fleet member alive without touching the restart budget
                print(f"supervise: replica drained cleanly (exit 0); "
                      f"relaunching in {backoff:.1f}s (not charged to "
                      f"the restart budget)", flush=True)
                sleep(backoff)
                continue
            print("supervise: trainer exited cleanly", flush=True)
            return 0
        if peer_restart:
            # the failing host's supervisor pays the budget; we just follow
            print(f"supervise: relaunching for peer-initiated pod restart "
                  f"in {backoff:.1f}s", flush=True)
            sleep(backoff)
            continue
        if epoch_file:
            if _read_epoch(epoch_file) > launch_epoch:
                # our failure is part of a pod-wide event a peer already
                # bumped for (coordinated preemption lands every child
                # within seconds): compounding the bump would advance the
                # epoch N times and SIGTERM peers' freshly resumed
                # trainers — follow the existing restart on their budget
                print("supervise: pod restart epoch already bumped for "
                      "this incarnation; following the peer-initiated "
                      f"restart in {backoff:.1f}s", flush=True)
                sleep(backoff)
                continue
            # our child failed first: tell the other hosts' supervisors to
            # restart their ranks too, so the pod relaunches together
            _bump_epoch(epoch_file, launch_epoch)
        verdict = budget.record(time.monotonic() - t0,
                                preempted=rc == EXIT_PREEMPTED,
                                stalled=stalled)
        if verdict is None:
            code = _shell_code(rc)
            print(f"supervise: restart budget exhausted; propagating exit "
                  f"code {code}", flush=True)
            return code
        kind, delay = verdict
        print(f"supervise: exit code {rc}"
              f"{' (stall-killed)' if stalled else ''}; {kind} in "
              f"{delay:.1f}s", flush=True)
        sleep(delay)


def _pod_exit_code(rcs, stalled: bool) -> int:
    """The single code a scheduler sees for a pod: a real crash wins over
    75 (something is wrong beyond preemption), 75 over a stall kill.
    Among crashes, a child's own verdict (77, then any other positive
    code) wins over codes synthesized from the supervisor's straggler
    SIGTERM — a reaped -15 must not mask the root cause."""
    crash = [rc for rc in rcs if rc not in (0, EXIT_PREEMPTED)]
    if crash:
        if EXIT_CLUSTER_FAILED in crash:
            return EXIT_CLUSTER_FAILED
        positive = [rc for rc in crash if rc > 0]
        return _shell_code(positive[0] if positive else crash[0])
    if any(rc == EXIT_PREEMPTED for rc in rcs):
        return EXIT_PREEMPTED
    return 1 if stalled else 0


def run_pod(cmd, num_procs: int, max_restarts: int = 3, backoff: float = 1.0,
            backoff_max: float = 60.0, heartbeat: str = "",
            stall_timeout: float = 0.0, term_grace: float = 10.0,
            poll_interval: float = 0.2, healthy_reset: float = 600.0,
            quota_window: float = 0.0, quota_backoff: float = 30.0,
            quota_backoff_max: float = 1800.0, max_launch_retries: int = 120,
            coordinator: str = "", metrics_jsonl: str = "",
            sleep=time.sleep) -> int:
    """Supervise an N-process local pod of ``cmd``; returns the exit code
    to propagate. The pod restarts as a unit (see the module docstring);
    restart accounting is shared across ranks through one budget."""
    budget = _RestartBudget(
        max_restarts, backoff, backoff_max, healthy_reset=healthy_reset,
        quota_window=quota_window, quota_backoff=quota_backoff,
        quota_backoff_max=quota_backoff_max,
        max_launch_retries=max_launch_retries)
    while True:
        launched_at = time.time()
        t0 = time.monotonic()
        print(f"supervise: launching pod of {num_procs} (restarts used "
              f"{budget.attempt}/{max_restarts}): {' '.join(cmd)}",
              flush=True)
        procs, hbs = [], []
        for i in range(num_procs):
            env = dict(os.environ)
            env["JAX_NUM_PROCESSES"] = str(num_procs)
            env["JAX_PROCESS_ID"] = str(i)
            env["PICOTRON_POD_RANK"] = str(i)
            if coordinator:
                env["JAX_COORDINATOR_ADDRESS"] = coordinator
            hb = f"{heartbeat}.p{i}" if heartbeat else ""
            if hb:
                env["PICOTRON_HEARTBEAT"] = hb
                _touch(hb)
            if metrics_jsonl:
                # only the controller rank writes metrics (train gates on
                # is_main_process), but export per-rank paths anyway so a
                # misconfigured pod can never interleave one file
                env["PICOTRON_METRICS_JSONL"] = (
                    metrics_jsonl if i == 0 else f"{metrics_jsonl}.p{i}")
            hbs.append(hb)
            # (as in run(): the supervisor itself never initialises a backend)
            procs.append(subprocess.Popen(cmd, env=env))
        rcs: list = [None] * num_procs
        stalled = False

        def _refresh() -> None:
            for i, p in enumerate(procs):
                if rcs[i] is None:
                    rcs[i] = p.poll()

        def _reap_stragglers() -> None:
            for i, p in enumerate(procs):
                if rcs[i] is None:
                    print(f"supervise: terminating straggler rank {i}",
                          flush=True)
                    rcs[i] = _terminate(p, term_grace)

        while True:
            _refresh()
            if all(rc is not None for rc in rcs):
                break
            if any(rc not in (None, 0) for rc in rcs):
                # one rank is down. Its peers normally follow on their own
                # — consensus exit 75, or the cluster monitor's 77 — so
                # give them the grace window to record THEIR verdicts
                # (and finish coordinated saves) before the hammer.
                deadline = time.monotonic() + term_grace
                while time.monotonic() < deadline:
                    _refresh()
                    if all(rc is not None for rc in rcs):
                        break
                    sleep(poll_interval)
                _reap_stragglers()
                break
            if heartbeat and stall_timeout > 0:
                stale = [i for i, hb in enumerate(hbs)
                         if rcs[i] is None
                         and _heartbeat_age(hb, launched_at) > stall_timeout]
                if stale:
                    print(f"supervise: rank(s) {stale} heartbeat stale for "
                          f"> {stall_timeout}s; killing the pod", flush=True)
                    stalled = True
                    _reap_stragglers()
                    break
            sleep(poll_interval)
        print(f"supervise: pod exit codes {rcs}"
              f"{' (stall-killed)' if stalled else ''}", flush=True)
        if all(rc == 0 for rc in rcs) and not stalled:
            print("supervise: pod exited cleanly", flush=True)
            return 0
        preempted = (any(rc == EXIT_PREEMPTED for rc in rcs)
                     and all(rc in (0, EXIT_PREEMPTED) for rc in rcs))
        verdict = budget.record(time.monotonic() - t0, preempted=preempted,
                                stalled=stalled)
        if verdict is None:
            code = _pod_exit_code(rcs, stalled)
            print(f"supervise: restart budget exhausted; propagating exit "
                  f"code {code}", flush=True)
            return code
        kind, delay = verdict
        what = "preempted (resumable)" if preempted else "failed"
        print(f"supervise: pod {what}; {kind} in {delay:.1f}s", flush=True)
        sleep(delay)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="bounded-restart watchdog around a trainer command "
                    "(everything after -- is the command line)")
    parser.add_argument("--max-restarts", type=int, default=3)
    parser.add_argument("--backoff", type=float, default=1.0,
                        help="first restart delay; doubles per restart")
    parser.add_argument("--backoff-max", type=float, default=60.0)
    parser.add_argument("--heartbeat", default="",
                        help="heartbeat file (exported as PICOTRON_HEARTBEAT;"
                             " pod mode appends .p<rank>)")
    parser.add_argument("--stall-timeout", type=float, default=0.0,
                        help="seconds of stale heartbeat before a stall kill "
                             "(0 = off)")
    parser.add_argument("--term-grace", type=float, default=10.0,
                        help="seconds between SIGTERM and SIGKILL on a stall "
                             "(pod mode: also how long peers may finish a "
                             "coordinated exit after a rank goes down)")
    parser.add_argument("--healthy-reset", type=float, default=600.0,
                        help="seconds of uptime after which the restart "
                             "budget and backoff reset (0 = never)")
    parser.add_argument("--quota-window", type=float, default=0.0,
                        help="a run dying within this many seconds of launch "
                             "is a quota-style launch failure: long backoff, "
                             "no restart-budget charge (0 = off)")
    parser.add_argument("--quota-backoff", type=float, default=30.0,
                        help="first launch-failure delay; doubles per failure")
    parser.add_argument("--quota-backoff-max", type=float, default=1800.0)
    parser.add_argument("--max-launch-retries", type=int, default=120,
                        help="consecutive launch failures before giving up "
                             "(0 = unlimited)")
    parser.add_argument("--serve", action="store_true",
                        help="the child is a serving replica "
                             "(tools/serve.py): clean drains (exit 0) "
                             "relaunch WITHOUT charging the restart "
                             "budget, nonzero exits walk the normal "
                             "ladder, and the supervisor's own "
                             "SIGTERM/SIGINT forwards to the child and "
                             "ends supervision after its drain")
    parser.add_argument("--num-procs", type=int, default=1,
                        help="N > 1 supervises a local N-process pod "
                             "(JAX_PROCESS_ID/JAX_NUM_PROCESSES per rank)")
    parser.add_argument("--coordinator", default="",
                        help="pod mode: exported as JAX_COORDINATOR_ADDRESS")
    parser.add_argument("--epoch-file", default="",
                        help="per-host pods: shared restart-epoch file; a "
                             "bump by any host restarts every host's child")
    parser.add_argument("--metrics-jsonl", default="",
                        help="per-step metrics JSONL path exported as "
                             "PICOTRON_METRICS_JSONL (point it next to the "
                             "run log; extract_metrics.py prefers it over "
                             "the log regex; pod ranks > 0 get .p<rank>)")
    parser.add_argument("cmd", nargs=argparse.REMAINDER,
                        help="-- then the command to supervise")
    args = parser.parse_args(argv)
    cmd = args.cmd
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        parser.error("no command given (usage: supervise [opts] -- cmd ...)")
    if args.stall_timeout > 0 and not args.heartbeat:
        parser.error("--stall-timeout needs --heartbeat")
    if args.serve and args.num_procs > 1:
        parser.error("--serve supervises one replica per supervisor "
                     "(run N supervisors for an N-replica fleet); it is "
                     "incompatible with --num-procs pods")
    if args.num_procs > 1 and args.epoch_file:
        parser.error("--epoch-file is for one-supervisor-per-host pods; "
                     "--num-procs already restarts its local pod together")
    if args.num_procs > 1 and not args.coordinator:
        # without JAX_COORDINATOR_ADDRESS the trainer never joins a pod:
        # N full DUPLICATE single-process runs would race on one save_dir
        parser.error("--num-procs needs --coordinator (host:port for the "
                     "ranks' jax.distributed rendezvous)")
    common = dict(
        max_restarts=args.max_restarts, backoff=args.backoff,
        backoff_max=args.backoff_max, heartbeat=args.heartbeat,
        stall_timeout=args.stall_timeout, term_grace=args.term_grace,
        healthy_reset=args.healthy_reset, quota_window=args.quota_window,
        quota_backoff=args.quota_backoff,
        quota_backoff_max=args.quota_backoff_max,
        max_launch_retries=args.max_launch_retries,
        metrics_jsonl=args.metrics_jsonl)
    if args.num_procs > 1:
        return run_pod(cmd, args.num_procs, coordinator=args.coordinator,
                       **common)
    return run_supervised(cmd, epoch_file=args.epoch_file,
                          serve_mode=args.serve, **common)


if __name__ == "__main__":
    sys.exit(main())
