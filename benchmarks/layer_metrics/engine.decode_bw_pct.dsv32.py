"""The decode step of the DeepSeek-V3.2 block against the HBM roofline,
memory bound: ``opcount_dsv32.decode_step_bytes`` (the weights outside the
routed experts once, the held experts a layer's step reaches, the indexer's
key of every live token, ``min(context, index_topk)`` latent rows a slot) /
819 GB/s / the step's device time (``stats.decode_runs``: the
``_decode_block_impl`` runs of the traced stretch). Live tokens and rows are
those of the requests streaming in the traced stretch; the experts hit a
layer-step come from the window's two scrapes
(``picotron_moe_experts_hit_total`` / ``picotron_moe_layer_steps_total``).
A program without those counters reads as nothing."""

import bisect

from benchmarks import opcount_dsv32, phases, stats


def selected_rows(requests, topk: int, t0: float, t1: float,
                  samples: int = 200) -> float:
    """Mean over [t0, t1] of ``min(context, topk)`` summed over the requests
    streaming at that instant."""
    total = 0.0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        for r in requests:
            tt = r["token_times"]
            if tt and tt[0] <= t <= r.get("done", tt[-1]):
                total += min(topk, r["prompt_len"]
                             + bisect.bisect_right(tt, t))
    return total / samples


def read(run):
    got = stats.decode_runs(run)
    if got is None or "peaks" not in run or "metrics_after" not in run:
        return None
    layer_steps = phases.delta(run, "picotron_moe_layer_steps_total")
    if layer_steps <= 0:
        return None
    hit = phases.delta(run, "picotron_moe_experts_hit_total") / layer_steps
    seconds, steps = got
    trace, model = run["trace"], run["config"]
    reqs = run["load"]["requests"]
    live = stats.live_tokens(reqs, trace["t_start"], trace["t_stop"])
    rows = selected_rows(reqs, model["index_topk"], trace["t_start"],
                         trace["t_stop"])
    least = opcount_dsv32.decode_step_bytes(model, live, rows, hit) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
