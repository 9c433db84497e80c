"""Share of the traced span in which a collective op (all-gather,
reduce-scatter, all-reduce, collective-permute) ran on device 0: union of
their intervals over the span from the first device op to the last. It
counts a collective whether or not compute hid it."""


def read(run):
    trace = run.get("trace")
    if not trace or "steps" not in run or not trace.get("span_s"):
        return None
    return 100.0 * trace["collective_s"] / trace["span_s"]
