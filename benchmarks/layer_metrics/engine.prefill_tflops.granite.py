"""What the prefill programs of the Granite-4.0-H block deliver of the
FLOPs their tokens need, a second of prefill dispatch, in TFLOP/s:
``opcount_granite.prefill_flops_per_token`` (the routed share of the held
experts, not every held expert over the whole chunk, which is what the
program runs today) x delta ``picotron_prefill_tokens_total``, + the head
once a prompt that got its first token in the window, / delta
``picotron_dispatch_seconds_sum{kind="prefill"}`` (enqueue to the first
token on the host). A program without the block's counters reads as
nothing."""

from benchmarks import opcount_granite, phases, stats


def read(run):
    load = run.get("load")
    if not load or "metrics_after" not in run:
        return None
    if phases.delta(run, "picotron_ssm_tokens_scanned_total") <= 0:
        return None
    seconds = phases.delta(run, "picotron_dispatch_seconds_sum",
                           kind="prefill")
    tokens = phases.delta(run, "picotron_prefill_tokens_total")
    t0, t1 = stats.window(load)
    lens = [r["prompt_len"] for r in load["requests"]
            if r["token_times"] and t0 <= r["token_times"][0] <= t1]
    if seconds <= 0 or tokens <= 0 or not lens:
        return None
    # a prompt's tokens see half of it on average, the long ones more
    context = sum(n * n for n in lens) / (2.0 * sum(lens))
    flops = (tokens * opcount_granite.prefill_flops_per_token(
        run["config"], context)
        + len(lens) * opcount_granite.head_flops(run["config"]))
    return flops / seconds / 1e12
