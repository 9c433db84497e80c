"""The decode step of the Trinity block against the HBM roofline, memory
bound: ``opcount_afmoe.decode_step_bytes`` (every weight but the embedding
table once, every held expert as run; K and V of every live token in the
full layers; of the last ``sliding_window`` tokens of each live slot in the
sliding ones) / 819 GB/s / the step's device time (``stats.decode_runs``:
the ``_decode_block_impl`` runs of the traced stretch). The contexts are
those of the requests streaming in the traced stretch, a slot at a time.
A program without the block's counters (``picotron_swa_layer_steps_total``)
reads as nothing."""

import bisect

from benchmarks import opcount_afmoe, phases, stats


def mean_over(requests, t0: float, t1: float, fn, samples: int = 200):
    """Mean over [t0, t1] of ``fn(contexts)``, the contexts (prompt plus
    tokens so far) of the requests streaming at that instant."""
    total = 0.0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        total += fn([r["prompt_len"] + bisect.bisect_right(r["token_times"], t)
                     for r in requests if r["token_times"]
                     and r["token_times"][0] <= t
                     <= r.get("done", r["token_times"][-1])])
    return total / samples


def read(run):
    got = stats.decode_runs(run)
    if got is None or "peaks" not in run or "metrics_after" not in run:
        return None
    if phases.delta(run, "picotron_swa_layer_steps_total") <= 0:
        return None
    seconds, steps = got
    trace = run["trace"]
    least = mean_over(
        run["load"]["requests"], trace["t_start"], trace["t_stop"],
        lambda ctx: opcount_afmoe.decode_step_bytes(run["config"], ctx)) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
