"""``sync/wait``: the host blocked until the round's outputs are ready on the
device (the launch, then the program's run as the host sees it), inside
``step/sync``; mean ms a round over the whole window, profiler off."""

from benchmarks import parts


def read(run):
    return parts.part_ms_a_round(run, "sync/wait")
