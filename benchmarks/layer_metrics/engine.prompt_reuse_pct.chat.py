"""Share of the prompt tokens asked that no prefill program ran, for the cells
that report ``serve_tpot_mean_ms``: 100 x (1 - delta
``picotron_prefill_tokens_total`` between the window's two scrapes / prompt
tokens of the requests whose first token arrived inside the window). A cache
that shares nothing reads 0 within the window's edges (a prefill on one side
of a scrape, its first token on the other); a prefix cache that holds a
document until it is asked again moves it, and with it the prefill stalls in
``serve_tpot_mean_ms``."""

from benchmarks import phases, stats


def read(run):
    load = run.get("load")
    if not load or "metrics_after" not in run:
        return None
    t0, t1 = stats.window(load)
    asked = sum(r["prompt_len"] for r in load["requests"]
                if r["token_times"] and t0 <= r["token_times"][0] <= t1)
    if not asked:
        return None
    ran = phases.delta(run, "picotron_prefill_tokens_total")
    return 100.0 * (1.0 - ran / asked)
