"""Client side: time per output token after the first, (last token - first
token) / (tokens - 1) of one request, mean over the requests due inside the
window. It holds the decode step, the host gap between dispatches and every
stall a prefill or the queue puts into a running stream, and a longer
decode block cannot flatter it. The tails of an open loop below the knee
(TTFT p90, inter-token p99) swing by tens of percent between two runs of one
seed on this server (PERF.md), so this is the bounded metric there and they
are read beside it."""

import statistics

from benchmarks import stats


def read(run):
    load = run.get("load")
    if not load:
        return None
    per = [(r["token_times"][-1] - r["token_times"][0])
           / (len(r["token_times"]) - 1)
           for r in stats.due_in_window(load) if len(r["token_times"]) > 1]
    return 1e3 * statistics.mean(per) if per else None
