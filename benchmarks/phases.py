"""Readers of the server's labelled families between the window's two
``GET /metrics`` scrapes: the dispatch loop's phases
(``picotron_round_phase_seconds{phase=...}``, one observation a round) and
the prefill counters. A program that lacks a family (the parent of the PR
that added it) reads as nothing, never as an error."""

from __future__ import annotations

import re

_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(?:\{([^}]*)\})?\s+(\S+)$")


def labelled(text: str, name: str, **labels) -> float:
    """Sum of the samples called ``name`` that carry every given label."""
    want = [f'{k}="{v}"' for k, v in labels.items()]
    total = 0.0
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if m and m.group(1) == name \
                and all(w in (m.group(2) or "").split(",") for w in want):
            total += float(m.group(3))
    return total


def delta(run, name: str, **labels) -> float:
    return (labelled(run["metrics_after"], name, **labels)
            - labelled(run["metrics_before"], name, **labels))


def phase_mean_ms(run, *phases) -> float | None:
    """Mean host time a round, in ms, of the named phases together: for
    each, the histogram's sum over its count between the scrapes (exact;
    every phase is observed once a round)."""
    if "metrics_after" not in run:
        return None
    total = 0.0
    for phase in phases:
        n = delta(run, "picotron_round_phase_seconds_count", phase=phase)
        if n <= 0:
            return None
        total += delta(run, "picotron_round_phase_seconds_sum",
                       phase=phase) / n
    return 1e3 * total


def prefill_tokens_per_s(run) -> float | None:
    """Prompt tokens run through the solo and chunked prefill programs
    (cached prefix excluded, the fused lane's tokens taken off: their time
    is the decode dispatch's) over the wall time of those dispatches, from
    the enqueue to the first token's arrival on the host."""
    if "metrics_after" not in run:
        return None
    seconds = delta(run, "picotron_dispatch_seconds_sum", kind="prefill")
    tokens = (delta(run, "picotron_prefill_tokens_total")
              - delta(run, "picotron_prefill_lane_tokens_total"))
    return tokens / seconds if seconds > 0 and tokens > 0 else None
