"""``issue/enqueue``: the call of the compiled round program to its return
(argument handling, the donated cache, the runtime's enqueue), inside
``step/issue``; mean ms a round, profiler off."""

from benchmarks import parts


def read(run):
    return parts.part_ms_a_round(run, "issue/enqueue")
