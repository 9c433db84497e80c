"""The decode step of the Granite-4.0-H block against the HBM roofline,
memory bound: ``opcount_granite.decode_step_bytes`` (every weight once, the
embedding as the head; each live slot's recurrent state read and written;
K and V of every live token in the attention layers) / 819 GB/s / the step's
device time (``stats.decode_runs``: the ``_decode_block_impl`` runs of the
traced stretch). Live slots and tokens are those of the requests streaming
in the traced stretch. A program without the block's counters
(``picotron_ssm_layer_steps_total``) reads as nothing."""

from benchmarks import opcount_granite, phases, stats


def live_slots(requests, t0: float, t1: float, samples: int = 200) -> float:
    """Mean over [t0, t1] of the requests streaming at that instant."""
    total = 0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        total += sum(1 for r in requests if r["token_times"]
                     and r["token_times"][0] <= t
                     <= r.get("done", r["token_times"][-1]))
    return total / samples


def read(run):
    got = stats.decode_runs(run)
    if got is None or "peaks" not in run or "metrics_after" not in run:
        return None
    if phases.delta(run, "picotron_ssm_layer_steps_total") <= 0:
        return None
    seconds, steps = got
    trace, reqs = run["trace"], run["load"]["requests"]
    span = trace["t_start"], trace["t_stop"]
    least = opcount_granite.decode_step_bytes(
        run["config"], live_slots(reqs, *span),
        stats.live_tokens(reqs, *span)) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
