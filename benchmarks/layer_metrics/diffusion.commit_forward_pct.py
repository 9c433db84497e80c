"""The share of a blocks engine's forwards that are commit forwards (a
finished block forwarded once more so that its K/V stay): 100 x delta
``picotron_diffusion_forwards_total{kind="commit"}`` / delta of both kinds,
between the window's two scrapes; 20 at five forwards a block. What fusing a
commit into the next block's first denoise forward would take away. A
program without the counters reads as nothing."""

from benchmarks import phases

NAME = "picotron_diffusion_forwards_total"


def read(run):
    if "metrics_after" not in run:
        return None
    forwards = phases.delta(run, NAME)
    if forwards <= 0:
        return None
    return 100.0 * phases.delta(run, NAME, kind="commit") / forwards
