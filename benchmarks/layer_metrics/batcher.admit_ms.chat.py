"""``step/admit``: a round's time inside admission, mean ms a round, for the
cells that report ``serve_tpot_mean_ms``. With ``mixed_dispatch`` off a
prompt is prefilled there, serially, before the round's decode block is
issued, so this is the stall a round's admissions put into every running
stream: a few ms where prompts are short and rare, most of a round where
every request brings a long document. ``batcher.dispatch_gap_ms.chat`` runs
from a round's sync end to the next issue and so holds this as well: it
means the host's own share only where this is small."""

from benchmarks import phases


def read(run):
    return phases.phase_mean_ms(run, "step/admit")
