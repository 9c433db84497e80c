"""Small arithmetic the metric readers share."""

from __future__ import annotations

import bisect
import math
import re


def percentile(values, p: float) -> float | None:
    """Nearest-rank percentile: the smallest value with at least ``p``
    percent of the sample at or below it."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[max(0, math.ceil(p / 100.0 * len(vals)) - 1)]


def window(load: dict) -> tuple:
    return load["t0"], load["t0"] + load["seconds"]


def token_gaps(load: dict) -> list:
    """Seconds between consecutive streamed tokens of one request, for
    every token that arrived inside the window."""
    t0, t1 = window(load)
    return [b - a for r in load["requests"]
            for a, b in zip(r["token_times"], r["token_times"][1:])
            if t0 <= b <= t1]


def due_in_window(load: dict) -> list:
    """The requests that were due (closed loop: sent) inside the window."""
    t0, t1 = window(load)
    return [r for r in load["requests"] if t0 <= r["due"] <= t1]


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)$")


def prom_values(text: str, name: str) -> float:
    """Sum over label sets of the samples called exactly ``name``."""
    total = 0.0
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if m and m.group(1) == name:
            total += float(m.group(3))
    return total


def histogram_mean_delta(before: str, after: str, name: str) -> float | None:
    """Mean of a Prometheus histogram's observations between two scrapes:
    delta of ``_sum`` over delta of ``_count``, exact (its power-of-two
    buckets would place a median only within a factor of two)."""
    n = prom_values(after, name + "_count") - prom_values(before,
                                                          name + "_count")
    if n <= 0:
        return None
    return (prom_values(after, name + "_sum")
            - prom_values(before, name + "_sum")) / n


DECODE_PROGRAMS = ("_decode_block_impl", "_decode_impl")


def decode_runs(run) -> tuple | None:
    """(seconds, steps) of the decode programs' runs in the trace (``XLA
    Modules`` line, device 0): a block program runs ``decode_block_len``
    steps, the per-token program one."""
    trace = run.get("trace")
    if not trace or "load" not in run:
        return None
    hits = [(k, v) for k, v in trace["modules"].items()
            if any(p in k for p in DECODE_PROGRAMS)]
    steps = sum(v[0] * (run["decode_block_len"] if "_block_" in k else 1)
                for k, v in hits)
    return (sum(v[1] for _, v in hits), steps) if steps else None


def live_tokens(requests, t0: float, t1: float, samples: int = 200) -> float:
    """Mean over [t0, t1] of the tokens cached in live slots: prompt plus
    tokens so far of every request streaming at that instant."""
    total = 0.0
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        for r in requests:
            tt = r["token_times"]
            if tt and tt[0] <= t <= r.get("done", tt[-1]):
                total += r["prompt_len"] + bisect.bisect_right(tt, t)
    return total / samples


def idle_pct(run) -> float | None:
    """Share of the traced stretch in which no operation ran on the device:
    1 - union of the device-op intervals / traced seconds, mean of the
    chips."""
    trace = run.get("trace")
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def window_mean_ms(run, histogram: str) -> float | None:
    """Mean, in ms, of one of the server's histograms over the window
    (``GET /metrics`` at its start and end)."""
    if "metrics_after" not in run:
        return None
    m = histogram_mean_delta(run["metrics_before"], run["metrics_after"],
                             histogram)
    return None if m is None else 1e3 * m


def dispatch_gap_ms(run) -> float | None:
    """``picotron_dispatch_gap_seconds``: host time between one dispatch's
    results and the next dispatch."""
    return window_mean_ms(run, "picotron_dispatch_gap_seconds")


def itl_p99_ms(run) -> float | None:
    """99th percentile of the gap between consecutive streamed tokens of
    one request, over every token that arrived inside the window."""
    load = run.get("load")
    p = percentile(token_gaps(load), 99) if load else None
    return None if p is None else 1e3 * p


def decode_step_ms(run) -> float | None:
    """Device time of one decode step: the decode programs' runs in the
    trace over the steps they executed."""
    got = decode_runs(run)
    return None if got is None else 1e3 * got[0] / got[1]


def decode_bw_pct(run) -> float | None:
    """The decode step's share of the HBM roofline, memory bound: the bytes
    a step must read (every weight but the embedding once, plus K and V of
    every token cached in a live slot, from shapes, unpadded) over the
    chip's HBM bytes/s, over the step's device time."""
    from benchmarks import opcount

    got = decode_runs(run)
    if got is None or "peaks" not in run:
        return None
    seconds, steps = got
    trace = run["trace"]
    live = live_tokens(run["load"]["requests"], trace["t_start"],
                       trace["t_stop"])
    least = opcount.decode_step_bytes(run["config"], live) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / steps)
