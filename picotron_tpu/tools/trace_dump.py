"""Dump, validate, and query Chrome-trace JSON from the span tracer.

    # validate a dumped trace (train's obs.trace_path, or a saved /tracez)
    python -m picotron_tpu.tools.trace_dump trace.json

    # fetch from a live server and save
    python -m picotron_tpu.tools.trace_dump --url http://127.0.0.1:8000/tracez \
        --out trace.json

    # additionally require at least one COMPLETE request chain
    # (queue/prefill -> >=1 dispatch -> delivery, all parented) — the
    # `make obs-smoke` gate
    python -m picotron_tpu.tools.trace_dump trace.json --require-request-chain

    # the spans of a round the stall judge found slow (pinned beside the
    # ring, so they are there after it has turned over)
    python -m picotron_tpu.tools.trace_dump trace.json --stall-round 1234

The file format is the Chrome trace-event "traceEvents" array
(chrome://tracing, https://ui.perfetto.dev both load it directly);
``picotron_tpu.obs.tracing.SpanTracer.chrome_trace`` emits it with
``args.id``/``args.parent`` carrying the span links. ``validate`` checks
structure (every event named, timestamped, complete events carry ``dur``);
``dangling_parents`` reports unresolved parent links as WARNINGS only — a
live ``/tracez`` snapshot legitimately has them (an in-flight request's
root span isn't in the ring until it ends, and ring eviction drops old
roots); ``request_chains`` reassembles each request's tree. Exit 1 on any
validation error (or a missing required chain), so the smoke targets can
gate on it.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional

# span names the batcher/front end record under a request root
_CHAIN_DISPATCH = ("decode", "verify")


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def fetch(url: str) -> dict:
    """GET a /tracez endpoint (stdlib only)."""
    from urllib.request import urlopen

    with urlopen(url, timeout=60) as resp:
        return json.loads(resp.read())


def validate(trace: dict) -> list:
    """Structural errors in a Chrome-trace dict ([] = valid)."""
    errors = []
    events = trace.get("traceEvents")
    if not isinstance(events, list):
        return ["top-level 'traceEvents' must be a list"]
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        for field in ("name", "ph", "ts", "pid", "tid"):
            if field not in ev:
                errors.append(f"event {i}: missing {field!r}")
        if not isinstance(ev.get("ts", 0), (int, float)):
            errors.append(f"event {i}: non-numeric ts")
        if ev.get("ph") == "X":
            if not isinstance(ev.get("dur"), (int, float)) or ev["dur"] < 0:
                errors.append(f"event {i}: complete event without a "
                              f"non-negative dur")
    return errors


def dangling_parents(trace: dict) -> list:
    """Parent references that resolve to no event id in the trace.
    Reported as WARNINGS, not errors: a live ``/tracez`` snapshot
    legitimately contains them — a request's root span only lands in the
    ring when it ENDS, so an in-flight request's queue_wait/prefill/
    dispatch children reference a root that isn't exported yet, and ring
    eviction on a busy server drops old roots before their children."""
    events = [e for e in trace.get("traceEvents", ())
              if isinstance(e, dict)]
    ids = {(e.get("args") or {}).get("id") for e in events}
    out = []
    for i, ev in enumerate(events):
        parent = (ev.get("args") or {}).get("parent")
        if parent is not None and parent not in ids:
            out.append(
                f"event {i} ({ev.get('name')!r}): parent {parent} does "
                f"not resolve to any event id in the trace (in-flight "
                f"request or evicted root?)")
    return out


def request_chains(trace: dict) -> dict:
    """Reassemble per-request span trees: {uid: {"queue_wait", "prefill",
    "dispatches", "delivery", "complete"}}. A chain is COMPLETE when the
    request saw a prefill, at least one decode/verify dispatch child, and
    a delivery — all parented (directly) to the request root."""
    events = [e for e in trace.get("traceEvents", ())
              if isinstance(e, dict)]
    roots = {}  # span id -> uid
    for ev in events:
        args = ev.get("args") or {}
        if ev.get("name") == "request" and "uid" in args:
            roots[args.get("id")] = args["uid"]
    chains = {uid: {"queue_wait": False, "prefill": False,
                    "dispatches": 0, "delivery": False}
              for uid in roots.values()}
    for ev in events:
        args = ev.get("args") or {}
        uid = roots.get(args.get("parent"))
        if uid is None:
            continue
        c = chains[uid]
        name = ev.get("name")
        if name == "queue_wait":
            c["queue_wait"] = True
        elif name == "prefill":
            c["prefill"] = True
        elif name in _CHAIN_DISPATCH:
            c["dispatches"] += 1
        elif name == "delivery":
            c["delivery"] = True
    for c in chains.values():
        c["complete"] = bool(c["prefill"] and c["dispatches"]
                             and c["delivery"])
    return chains


def stall_rounds(trace: dict) -> dict:
    """{round: events} of the slow rounds whose spans the stall judge
    pinned (``SpanTracer.pin``: each carries ``args.stall_round`` and
    ``args.stall_where``, docs/OBSERVABILITY.md "Stalls"), each round's
    events in time order."""
    out: dict = {}
    for ev in trace.get("traceEvents", ()):
        if not isinstance(ev, dict):
            continue
        args = ev.get("args") or {}
        if "stall_round" in args:
            out.setdefault(args["stall_round"], []).append(ev)
    for events in out.values():
        events.sort(key=lambda e: e.get("ts", 0))
    return out


def overlap_chain(trace: dict) -> dict:
    """Validate the overlapped-scheduling span chain (inference.overlap;
    docs/INFERENCE.md "Overlapped scheduling"): every ``overlap`` event
    must parent to a ``dispatch/*`` span and sit inside its parent's
    window — the witness that round N's sync/deliver stage ran while
    round N+1 executed on device. Returns {"overlaps", "linked",
    "errors"}; the obs-smoke overlap leg requires >= 1 linked and no
    errors (``--require-overlap-chain``)."""
    events = [e for e in trace.get("traceEvents", ())
              if isinstance(e, dict)]
    by_id = {}
    for e in events:
        sid = (e.get("args") or {}).get("id")
        if sid is not None:
            by_id[sid] = e
    out = {"overlaps": 0, "linked": 0, "errors": []}
    for i, ev in enumerate(events):
        if ev.get("name") != "overlap":
            continue
        out["overlaps"] += 1
        parent = by_id.get((ev.get("args") or {}).get("parent"))
        if parent is None:
            out["errors"].append(
                f"event {i}: overlap span has no resolvable parent")
            continue
        if not str(parent.get("name", "")).startswith("dispatch/"):
            out["errors"].append(
                f"event {i}: overlap parent is {parent.get('name')!r}, "
                f"expected a dispatch/* span")
            continue
        p0 = parent.get("ts", 0)
        p1 = p0 + parent.get("dur", 0)
        t0 = ev.get("ts", 0)
        t1 = t0 + ev.get("dur", 0)
        if t0 < p0 - 2 or t1 > p1 + 2:  # 2us slack: ts quantization
            out["errors"].append(
                f"event {i}: overlap window [{t0}, {t1}] escapes its "
                f"dispatch parent's [{p0}, {p1}]")
            continue
        out["linked"] += 1
    return out


def lane_chain(trace: dict) -> dict:
    """Validate the mixed-dispatch prefill-lane span chain
    (inference.mixed_dispatch; docs/INFERENCE.md "Mixed prefill–decode
    dispatch"): every ``lane`` event (one confirmed lane chunk) must
    parent to a ``request`` root, and per request the chunks must tile
    the prompt — chunk numbers 1..n with each chunk starting where the
    previous ended, the last one landing at the lane prefill span's
    ``prompt_tokens``. Returns {"lanes", "linked", "errors"}; the
    mixed obs gate requires >= 1 linked and no errors
    (``--require-lane-chain``)."""
    events = [e for e in trace.get("traceEvents", ())
              if isinstance(e, dict)]
    by_id = {}
    for e in events:
        sid = (e.get("args") or {}).get("id")
        if sid is not None:
            by_id[sid] = e
    # prompt length per request root, from the lane=True prefill span
    prompt_of = {}
    for e in events:
        args = e.get("args") or {}
        if (e.get("name") == "prefill" and args.get("lane")
                and "prompt_tokens" in args):
            prompt_of[args.get("parent")] = args["prompt_tokens"]
    out = {"lanes": 0, "linked": 0, "errors": []}
    per_root: dict = {}
    for i, ev in enumerate(events):
        if ev.get("name") != "lane":
            continue
        out["lanes"] += 1
        args = ev.get("args") or {}
        parent = by_id.get(args.get("parent"))
        if parent is None:
            out["errors"].append(
                f"event {i}: lane span has no resolvable parent")
            continue
        if parent.get("name") != "request":
            out["errors"].append(
                f"event {i}: lane parent is {parent.get('name')!r}, "
                f"expected a request root")
            continue
        if not all(k in args for k in ("chunk", "start", "end")):
            out["errors"].append(
                f"event {i}: lane span missing chunk/start/end args")
            continue
        if args["end"] <= args["start"]:
            out["errors"].append(
                f"event {i}: empty lane chunk window "
                f"[{args['start']}, {args['end']}]")
            continue
        per_root.setdefault(args.get("parent"), []).append((i, args))
        out["linked"] += 1
    for root, chunks in per_root.items():
        chunks.sort(key=lambda c: c[1]["chunk"])
        if [c[1]["chunk"] for c in chunks] != list(
                range(1, len(chunks) + 1)):
            out["errors"].append(
                f"request {root}: lane chunk numbers "
                f"{[c[1]['chunk'] for c in chunks]} are not 1..n")
            continue
        for (i, a), (_, b) in zip(chunks, chunks[1:]):
            if b["start"] != a["end"]:
                out["errors"].append(
                    f"request {root}: lane chunk {b['chunk']} starts at "
                    f"{b['start']}, previous ended at {a['end']}")
        want = prompt_of.get(root)
        if want is not None and chunks[-1][1]["end"] != want:
            out["errors"].append(
                f"request {root}: lane chunks end at "
                f"{chunks[-1][1]['end']}, prompt has {want} tokens")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate/query Chrome-trace JSON from the span "
                    "tracer (obs.tracing; docs/OBSERVABILITY.md)")
    ap.add_argument("path", nargs="?", help="trace JSON file")
    ap.add_argument("--url", help="fetch from a live /tracez endpoint "
                                  "instead of a file")
    ap.add_argument("--out", help="write the (fetched or loaded) trace "
                                  "back out — save a live /tracez")
    ap.add_argument("--require-request-chain", nargs="?", const="any",
                    default=None, metavar="UID",
                    help="fail unless a COMPLETE request chain exists "
                         "(prefill -> >=1 dispatch -> delivery); pass a "
                         "UID to require that specific request's")
    ap.add_argument("--require-overlap-chain", action="store_true",
                    help="fail unless >= 1 'overlap' span links to a "
                         "dispatch/* parent within its window (the "
                         "inference.overlap pipeline's obs-smoke gate)")
    ap.add_argument("--require-lane-chain", action="store_true",
                    help="fail unless >= 1 'lane' span links to a request "
                         "root with chunks tiling the prompt (the "
                         "inference.mixed_dispatch obs gate)")
    ap.add_argument("--stall-round", type=int, default=None, metavar="SEQ",
                    help="list the pinned spans of slow round SEQ (a "
                         "slow_interval event's ``round``); fail if the "
                         "trace holds none")
    args = ap.parse_args(argv)
    if not args.path and not args.url:
        ap.error("pass a trace file path or --url")

    trace = fetch(args.url) if args.url else load(args.path)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(trace, f)
    errors = validate(trace)
    for e in errors:
        print(f"INVALID: {e}", file=sys.stderr)
    for w in dangling_parents(trace):
        print(f"WARN: {w}", file=sys.stderr)
    n = len(trace.get("traceEvents", ()))
    chains = request_chains(trace)
    complete = sorted(u for u, c in chains.items() if c["complete"])
    print(f"{n} events, {len(chains)} request chains "
          f"({len(complete)} complete)")
    for uid, c in sorted(chains.items()):
        print(f"  {uid}: queue_wait={c['queue_wait']} "
              f"prefill={c['prefill']} dispatches={c['dispatches']} "
              f"delivery={c['delivery']} "
              f"{'COMPLETE' if c['complete'] else 'partial'}")
    stalls = stall_rounds(trace)
    for seq, events in sorted(stalls.items()):
        print(f"  slow round {seq} ({events[0]['args']['stall_where']}): "
              f"{len(events)} pinned spans")
    if errors:
        return 1
    if args.stall_round is not None:
        for ev in stalls.get(args.stall_round, ()):
            print(f"    {ev['ts'] / 1e6:.6f} s +{ev.get('dur', 0) / 1e3:.3f} "
                  f"ms {ev['name']}")
        if args.stall_round not in stalls:
            print(f"FAILED: no pinned spans of round {args.stall_round}",
                  file=sys.stderr)
            return 1
    want = args.require_request_chain
    if want is not None:
        ok = bool(complete) if want == "any" \
            else chains.get(want, {}).get("complete", False)
        if not ok:
            print(f"FAILED: no complete request chain"
                  f"{'' if want == 'any' else f' for uid {want!r}'}",
                  file=sys.stderr)
            return 1
    if args.require_overlap_chain:
        ov = overlap_chain(trace)
        print(f"overlap chain: {ov['overlaps']} spans, "
              f"{ov['linked']} linked")
        for e in ov["errors"]:
            print(f"FAILED: {e}", file=sys.stderr)
        if ov["errors"] or not ov["linked"]:
            if not ov["overlaps"]:
                print("FAILED: no overlap spans in trace "
                      "(was the server run with --overlap?)",
                      file=sys.stderr)
            return 1
    if args.require_lane_chain:
        la = lane_chain(trace)
        print(f"lane chain: {la['lanes']} spans, {la['linked']} linked")
        for e in la["errors"]:
            print(f"FAILED: {e}", file=sys.stderr)
        if la["errors"] or not la["linked"]:
            if not la["lanes"]:
                print("FAILED: no lane spans in trace "
                      "(was the server run with mixed_dispatch?)",
                      file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
