"""Median over the window's requests of the client's TTFT (from *sent*)
less the server's own ``ttft_s`` in the done row: HTTP, JSON and the
front end's threads."""

import statistics

from benchmarks import stats


def read(run):
    load = run.get("load")
    if not load:
        return None
    over = [r["token_times"][0] - r["sent"] - r["server_ttft_s"]
            for r in stats.due_in_window(load)
            if r.get("ok") and r.get("server_ttft_s") is not None]
    return 1e3 * statistics.median(over) if over else None
