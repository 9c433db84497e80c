"""``sync/fetch``: the round's outputs, ready on the device, copied to the
host (tokens and counts), inside ``step/sync``; mean ms a round, profiler
off."""

from benchmarks import parts


def read(run):
    return parts.part_ms_a_round(run, "sync/fetch")
