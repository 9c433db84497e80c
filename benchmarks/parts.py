"""Reader of ``picotron_round_part_seconds{part=...}``: what a round's
``step/issue`` and ``step/sync`` hold inside (``issue/operands``,
``issue/enqueue``, ``sync/wait``, ``sync/fetch``), between the window's two
``GET /metrics`` scrapes, profiler off. A part is observed once a dispatch
and a phase once a round, so the mean is taken over the rounds: a round
that dispatched twice (an isolation re-dispatch) holds both. A program
without the family (the parent of the PR that added it) reads as nothing,
never as an error."""

from __future__ import annotations

from benchmarks import phases

FAMILY = "picotron_round_part_seconds"


def part_ms_a_round(run, part: str) -> float | None:
    """Mean host time a round, in ms, of one part: the histogram's sum over
    the rounds that issued."""
    if "metrics_after" not in run:
        return None
    rounds = phases.delta(run, "picotron_round_phase_seconds_count",
                          phase="step/issue")
    if rounds <= 0 or phases.delta(run, FAMILY + "_count", part=part) <= 0:
        return None
    return 1e3 * phases.delta(run, FAMILY + "_sum", part=part) / rounds
