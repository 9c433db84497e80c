"""Median host time of one optimizer step (batch handed over, dispatch,
``block_until_ready`` of its loss)."""

import statistics


def read(run):
    if "steps" not in run:
        return None
    return 1e3 * statistics.median(s["t_end"] - s["t_start"]
                                   for s in run["steps"])
