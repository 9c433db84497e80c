"""The part of ``batcher.stall_s`` in which the host was blocked on a device
program: slow ``step/sync`` (the round) and slow ``step/admit`` (a prefill's
first token)."""

from benchmarks import stalls


def read(run):
    return stalls.stall_s(run, stalls.DEVICE_WAIT)
