"""``step/deliver``: from the sync's end to ``step()``'s return, the token
walk, ``_token_done``, the ``_on_token`` puts, ``_lane_land``; mean ms a
round."""

from benchmarks import phases


def read(run):
    return phases.phase_mean_ms(run, "step/deliver")
