"""Output tokens the clients received inside the window, over its seconds.
Only a saturated cell reports it: below the knee it equals the offered
load."""

from benchmarks import stats


def read(run):
    load = run.get("load")
    if not load:
        return None
    t0, t1 = stats.window(load)
    n = sum(t0 <= t <= t1 for r in load["requests"] for t in r["token_times"])
    return n / load["seconds"]
