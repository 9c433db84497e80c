"""``issue/operands``: the round's host rows (tokens, eos, budget,
temperature, top_k, top_p, the lane's operands) copied to the device, inside
``step/issue``; mean ms a round, profiler off."""

from benchmarks import parts


def read(run):
    return parts.part_ms_a_round(run, "issue/operands")
