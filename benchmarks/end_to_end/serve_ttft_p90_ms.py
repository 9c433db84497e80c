"""Client side: from the instant a request was *due* to its first streamed
token, 90th percentile over the requests due inside the window. A request
that failed, was refused or never ended counts as a miss: the drain limit."""

from benchmarks import stats


def read(run):
    load = run.get("load")
    if not load:
        return None
    miss = float(run["traffic"].get("drain_seconds", 60))
    ttfts = [r["token_times"][0] - r["due"]
             if r.get("ok") and r["token_times"] else miss
             for r in stats.due_in_window(load)] + [miss] * load["unfinished"]
    p = stats.percentile(ttfts, 90)
    return None if p is None else 1e3 * p
