"""``phases.prefill_tokens_per_s``: a prefill stalls every running stream, so
its rate moves ``serve_tpot_mean_ms``."""

from benchmarks import phases


def read(run):
    return phases.prefill_tokens_per_s(run)
