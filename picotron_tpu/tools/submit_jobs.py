"""Experiment sweep launcher with status triage.

Re-build of the reference's ``submit_slurm_jobs.py`` (:8-220): the same
Status lifecycle (INIT -> PENDING -> RUNNING -> {FAIL, OOM, TIMEOUT} ->
COMPLETED, :8-16), per-job ``status.txt`` persistence (:18-53), a Scheduler
that walks experiment directories for ``config.json`` files, submits each,
supports resubmission filtered by status class (``--only fail|oom|timeout|
pending|running``, :157-171), and tabulates status (:116-147).

Two backends replace the reference's sbatch-only path:

- ``local``: run ``python -m picotron_tpu.train`` as a subprocess on this
  host — the natural launcher for a single-controller TPU VM (one process
  drives all chips; there is no torchrun-style per-rank spawn to reproduce).
  Post-mortem log classification (the reference does this inside
  base_job.slurm:82-94 by grepping the log for OOM/timeout markers) happens
  here in Python with TPU-appropriate patterns (RESOURCE_EXHAUSTED etc.).
- ``slurm``: render ``template/base_job.slurm`` with jinja2 (reference
  :74-80) and sbatch it, with optional chained ``--dependency=afterany``
  arrays (:104-113,:175-199) for time-sliced TPU reservations.
"""

from __future__ import annotations

import argparse
import enum
import os
import re
import subprocess
import sys
import time
from typing import Optional


class Status(enum.Enum):
    # Lifecycle mirrors reference submit_slurm_jobs.py:8-16.
    INIT = "init"
    PENDING = "pending"
    RUNNING = "running"
    FAIL = "fail"
    OOM = "oom"
    TIMEOUT = "timeout"
    COMPLETED = "completed"


# Log patterns -> terminal status (TPU re-expression of the grep table in
# reference base_job.slurm:82-94). Only patterns that are definitive on a
# *failed* run belong here — benign allocator/retry lines ("Attempting to
# reserve", "Timed out waiting ... retrying") appear on healthy runs too.
OOM_PATTERNS = (
    "RESOURCE_EXHAUSTED",
    "Out of memory",
    "out of memory",
    "OOM when allocating",
)
TIMEOUT_PATTERNS = (
    "DEADLINE_EXCEEDED",
    "DUE TO TIME LIMIT",
    "collective operation timed out",
)


def classify_log(log_text: str, exit_code: Optional[int]) -> Status:
    # Exit code wins: warning substrings on a successful run are benign.
    if exit_code == 0:
        return Status.COMPLETED
    # exit_code None = the launcher killed the job at its wall-clock limit;
    # that is a timeout regardless of what the log accumulated.
    if exit_code is None:
        return Status.TIMEOUT
    for pat in OOM_PATTERNS:
        if pat in log_text:
            return Status.OOM
    for pat in TIMEOUT_PATTERNS:
        if pat in log_text:
            return Status.TIMEOUT
    return Status.FAIL


class Job:
    """One experiment directory: a config.json + status.txt + log file
    (reference Job, submit_slurm_jobs.py:18-53)."""

    def __init__(self, root: str):
        self.root = root
        self.config_path = os.path.join(root, "config.json")
        self.status_path = os.path.join(root, "status.txt")
        self.log_path = os.path.join(root, "log.out")
        self.name = os.path.basename(os.path.normpath(root))

    @property
    def status(self) -> Status:
        try:
            with open(self.status_path) as f:
                return Status(f.read().strip())
        except (FileNotFoundError, ValueError):
            return Status.INIT

    def set_status(self, status: Status) -> None:
        with open(self.status_path, "w") as f:
            f.write(status.value)

    def classify_from_log(self, exit_code: Optional[int]) -> Status:
        try:
            with open(self.log_path, errors="replace") as f:
                text = f.read()
        except FileNotFoundError:
            text = ""
        status = classify_log(text, exit_code)
        self.set_status(status)
        return status


class Scheduler:
    """Walk an input dir of experiment subdirectories and run/submit each
    (reference Scheduler, submit_slurm_jobs.py:55-199)."""

    def __init__(self, inp_dir: str, backend: str = "local",
                 template_path: Optional[str] = None, qos: str = "normal"):
        self.inp_dir = inp_dir
        self.backend = backend
        self.qos = qos
        self.template_path = template_path or os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "templates", "base_job.slurm")
        self.jobs = self._discover()

    def _discover(self) -> list[Job]:
        jobs = []
        for root, _dirs, files in sorted(os.walk(self.inp_dir)):
            if "config.json" in files and "/profiler" not in root:
                jobs.append(Job(root))
        return jobs

    def select(self, only: Optional[str]) -> list[Job]:
        """Filter by status class for resubmission (reference :157-171)."""
        if not only:
            return [j for j in self.jobs if j.status is Status.INIT]
        wanted = {Status(s.strip()) for s in only.split(",")}
        return [j for j in self.jobs if j.status in wanted]

    # ---- local backend ----

    def run_local(self, job: Job, timeout_s: Optional[float] = None,
                  extra_args: Optional[list[str]] = None) -> Status:
        job.set_status(Status.RUNNING)
        cmd = [sys.executable, "-m", "picotron_tpu.train",
               "--config", job.config_path] + (extra_args or [])
        with open(job.log_path, "w") as log:
            try:
                # one process per chip: this parent imports the package (and so jax) but
                # never initialises a backend, so the chip is free for the child
                proc = subprocess.run(
                    cmd, stdout=log, stderr=subprocess.STDOUT,
                    timeout=timeout_s, cwd=job.root,
                    env={**os.environ, "PYTHONPATH": os.pathsep.join(
                        filter(None, [os.getcwd(),
                                      os.environ.get("PYTHONPATH", "")]))})
                exit_code: Optional[int] = proc.returncode
            except subprocess.TimeoutExpired:
                log.write("\nsubmit_jobs: killed: DUE TO TIME LIMIT\n")
                exit_code = None
        return job.classify_from_log(exit_code)

    # ---- slurm backend ----

    def render_slurm(self, job: Job) -> str:
        """Render the job script (reference :74-80 renders base_job.slurm,
        computing nodes from world size; TPU hosts drive multiple chips so
        nodes = ceil(world / chips_per_host))."""
        import jinja2

        from picotron_tpu.config import Config

        cfg = Config.from_json(job.config_path)
        chips_per_host = int(os.environ.get("PICOTRON_CHIPS_PER_HOST", "4"))
        nodes = max(1, -(-cfg.world_size // chips_per_host))
        with open(self.template_path) as f:
            template = jinja2.Template(f.read())
        rendered = template.render(
            exp_name=job.name, nodes=nodes, world_size=cfg.world_size,
            config_path=os.path.abspath(job.config_path),
            root=os.path.abspath(job.root), qos=self.qos,
            # single source of truth for failure classification patterns
            oom_greps=" ".join(f"-e {p!r}" for p in OOM_PATTERNS),
            timeout_greps=" ".join(f"-e {p!r}" for p in TIMEOUT_PATTERNS))
        script_path = os.path.join(job.root, "job.slurm")
        with open(script_path, "w") as f:
            f.write(rendered)
        return script_path

    def submit_slurm(self, job: Job, dependency: Optional[str] = None) -> str:
        script = self.render_slurm(job)
        cmd = ["sbatch"]
        if dependency:
            cmd.append(f"--dependency=afterany:{dependency}")
        cmd.append(script)
        # PENDING before sbatch: the job script writes "running" at startup,
        # and writing after submission could overwrite that on a fast start.
        job.set_status(Status.PENDING)
        try:
            out = subprocess.run(cmd, capture_output=True, text=True, check=True)
        except subprocess.SubprocessError:
            job.set_status(Status.INIT)
            raise
        job_id = out.stdout.strip().split()[-1]
        return job_id

    # ---- top-level ops ----

    def submit(self, only: Optional[str] = None, chain: bool = False,
               timeout_s: Optional[float] = None) -> None:
        selected = self.select(only)
        if not selected:
            print("no jobs to submit")
            return
        last_id: Optional[str] = None
        for job in selected:
            if self.backend == "local":
                t0 = time.perf_counter()
                status = self.run_local(job, timeout_s=timeout_s)
                print(f"{job.name}: {status.value} "
                      f"({time.perf_counter() - t0:.1f}s) -> {job.log_path}")
            else:
                dep = last_id if chain else None
                last_id = self.submit_slurm(job, dependency=dep)
                print(f"{job.name}: submitted as {last_id}"
                      + (f" (after {dep})" if dep else ""))

    def check_status(self) -> dict[str, int]:
        """Tabulate job statuses (reference check_status :116-147)."""
        counts: dict[str, int] = {}
        width = max((len(j.name) for j in self.jobs), default=4)
        for job in self.jobs:
            s = job.status.value
            counts[s] = counts.get(s, 0) + 1
            print(f"{job.name:<{width}}  {s}")
        print("-" * (width + 12))
        for s, n in sorted(counts.items()):
            print(f"{s:<{width}}  {n}")
        print(f"{'total':<{width}}  {len(self.jobs)}")
        return counts


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description="Submit/triage experiment sweeps")
    p.add_argument("--inp_dir", required=True,
                   help="directory containing experiment subdirs with config.json")
    p.add_argument("--backend", choices=("local", "slurm"), default="local")
    p.add_argument("--only", default=None,
                   help="resubmit filter: comma list of fail,oom,timeout,"
                        "pending,running,init,completed")
    p.add_argument("--chain", action="store_true",
                   help="slurm: chain jobs with --dependency=afterany")
    p.add_argument("--timeout", type=float, default=None,
                   help="local: per-job wall-clock limit in seconds")
    p.add_argument("--check_status", action="store_true")
    p.add_argument("--template", default=None, help="slurm template path")
    args = p.parse_args(argv)

    sched = Scheduler(args.inp_dir, backend=args.backend,
                      template_path=args.template)
    if args.check_status:
        sched.check_status()
    else:
        sched.submit(only=args.only, chain=args.chain, timeout_s=args.timeout)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
