"""How late the generator sent: sent - due, 99th percentile. A starved
generator must not be read as a fast server."""

from benchmarks import stats


def read(run):
    load = run.get("load")
    if not load or run["traffic"].get("loop") != "open":
        return None
    p = stats.percentile([r["sent"] - r["due"] for r in stats.due_in_window(load)], 99)
    return None if p is None else 1e3 * p
