"""``step/deliver`` (``batcher.deliver_ms``) for the saturated cells that
``serve_out_tokens_per_s`` alone bounds: at 64 slots a round hands over 512
token events while the device has nothing queued."""

from benchmarks import phases


def read(run):
    return phases.phase_mean_ms(run, "step/deliver")
