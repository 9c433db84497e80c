"""Device time of one forward of a blocks engine's round program
(``forward_runs``: the ``_blocks_impl`` runs on the trace's ``XLA Modules``
line, over the forwards a run made). The forwards a run made are the
window's: delta ``picotron_diffusion_forwards_total`` / delta
``picotron_dispatch_total{kind="blocks"}``, ten where every block takes four
denoise forwards and a commit and a round two blocks (the traced tail offers
the same mix). A program without the counters reads as nothing."""

from benchmarks import phases

PROGRAM = "_blocks_impl"


def forward_runs(run):
    """(seconds, forwards) of the round program's runs in the trace."""
    trace = run.get("trace")
    if not trace or "metrics_after" not in run:
        return None
    rounds = phases.delta(run, "picotron_dispatch_total", kind="blocks")
    forwards = phases.delta(run, "picotron_diffusion_forwards_total")
    hits = [v for k, v in trace["modules"].items() if PROGRAM in k]
    runs = sum(v[0] for v in hits)
    if rounds <= 0 or forwards <= 0 or not runs:
        return None
    return sum(v[1] for v in hits), runs * forwards / rounds


def read(run):
    got = forward_runs(run)
    return None if got is None else 1e3 * got[0] / got[1]
