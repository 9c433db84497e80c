"""Tokens a live slot gains a forward of a blocks engine: delta
``picotron_diffusion_positions_unmasked_total`` / delta
``picotron_diffusion_forwards_total`` (denoise and commit together) / the
live slots a forward ran for (delta ``picotron_diffusion_rows_total`` / delta
forwards / ``block_length``), between the window's two scrapes. A block of 4
that takes four denoise forwards and one commit gives 0.8; where confidences
pass ``confidence_threshold`` and the dynamic rule fixes several positions a
forward, more. A program without the counters reads as nothing."""

from benchmarks import phases


def read(run):
    if "metrics_after" not in run:
        return None
    forwards = phases.delta(run, "picotron_diffusion_forwards_total")
    rows = phases.delta(run, "picotron_diffusion_rows_total")
    if forwards <= 0 or rows <= 0:
        return None
    slots = rows / forwards / run["config"]["block_length"]
    return (phases.delta(run, "picotron_diffusion_positions_unmasked_total")
            / forwards / slots)
