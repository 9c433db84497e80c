"""From a profiler trace to numbers: the benchmark's own reduction.

Reads what ``jax.profiler.ProfileData`` gives (planes -> lines -> events with
``start_ns`` and ``duration_ns``) and nothing else, so it needs no
tensorflow protobufs. On a device plane the ops of one line nest (a
``while`` holds its body's ops, a fusion its parts) and lines overlap
(``XLA Modules`` spans its ``XLA Ops``), so time is never summed over
events: busy time is the *union* of the op intervals, and an op's own time
is its duration less its children's.

What a v5e trace looks like (read by hand, PR 24) is written in PERF.md.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# TraceAnnotation spans that name device gaps: the benchmark's own, and the
# program's loop-thread phases (handler threads write "pt.req:", left out)
HOST_SPAN_PREFIX = ("bench:", "pt:")
COLLECTIVES = ("all-gather", "reduce-scatter", "all-reduce",
               "collective-permute", "all-to-all")


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(trace_dir: str):
    """ProfileData of the newest trace under ``trace_dir``, or None."""
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    return ProfileData.from_file(path) if path else None


def short_name(name: str) -> str:
    """An op's event name is its whole HLO line (``%fusion.294 = bf16[...]
    fusion(...)``), a module's carries a fingerprint (``jit__step(3253..)``):
    keep the op's or the program's own name."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name.split("(", 1)[0] if name.endswith(")") else name


def base_name(name: str) -> str:
    """``flash_fwd.17`` -> ``flash_fwd``: the compiler's numbering off."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def intervals(line) -> list:
    """[(start_s, end_s, name)] of a line's events, by start."""
    out = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
            short_name(e.name)) for e in line.events]
    out.sort(key=lambda t: (t[0], -t[1]))
    return out


def union(ivs) -> list:
    """Merged [(start, end)] of possibly nested or overlapping intervals."""
    merged = []
    for s, e, *_ in sorted(ivs):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def union_seconds(ivs) -> float:
    return sum(e - s for s, e in union(ivs))


def self_seconds(ivs) -> dict:
    """{name: [events, seconds]} of each event's own time: its duration
    less the time its children (events it holds, on the same line) cover."""
    out = {}
    stack = []  # (end, name, [own seconds])

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            n, t = out.get(name, (0, 0.0))
            out[name] = [n + 1, t + max(own[0], 0.0)]

    for s, e, name in ivs:
        close(s)
        if stack:
            stack[-1][2][0] -= (min(e, stack[-1][0]) - s)
        stack.append((e, name, [e - s]))
    close(float("inf"))
    return out


def by_base_name(ops: dict, base: str) -> tuple:
    """(events, own seconds) of every op whose name is ``base`` or
    ``base.<n>``."""
    hits = [v for k, v in ops.items() if base_name(k) == base]
    return sum(v[0] for v in hits), sum(v[1] for v in hits)


def device_planes(pd) -> list:
    planes = [p for p in pd.planes if p.name.startswith(DEVICE_PREFIX)]
    return sorted(planes, key=lambda p: _plane_index(p.name))


def _plane_index(name: str) -> int:
    tail = name[len(DEVICE_PREFIX):].split()[0]
    return int(tail) if tail.isdigit() else 1 << 30


def line_named(plane, name: str):
    for ln in plane.lines:
        if ln.name == name:
            return ln
    return None


def host_spans(pd, prefix: str = HOST_SPAN_PREFIX) -> list:
    """[(start_s, end_s, name)] of the benchmark's own annotations on any
    host plane."""
    out = []
    for p in pd.planes:
        if p.name.startswith(DEVICE_PREFIX):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    out.append((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, e.name))
    return sorted(out)


def name_gaps(busy, spans, lo, hi) -> dict:
    """{name: idle seconds}: each gap of ``busy`` inside [lo, hi] is split
    among the host spans that overlap it; what no span covers is
    ``unnamed``. Nested spans: the innermost (latest start) wins."""
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    out = {}
    for gs, ge in gaps:
        if ge <= gs:
            continue
        cuts = sorted({gs, ge, *[t for s, e, _ in spans for t in (s, e)
                                 if gs < t < ge]})
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            inside = [sp for sp in spans if sp[0] <= mid < sp[1]]
            name = max(inside)[2] if inside else "unnamed"
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce(pd, window_s: float, n_devices: int) -> dict | None:
    """The run's ``trace`` record, or None when no device op was traced.

    ``busy_s``: union of the op intervals, averaged over the first
    ``n_devices`` device planes. ``window_s``: the traced window, as the
    caller's clock had it. ``ops``: {name: [events, own seconds]} on device
    0. ``modules``: {name: [runs, seconds]} of the programs on device 0.
    ``device_ops`` / ``idle_gaps``: the ten largest, for the ledger.
    """
    planes = device_planes(pd)[:n_devices]
    per_dev = []
    for p in planes:
        ln = line_named(p, OPS_LINE)
        per_dev.append(intervals(ln) if ln is not None else [])
    if not per_dev or not any(per_dev):
        return None
    busy = [union_seconds(ivs) for ivs in per_dev]
    ivs0 = per_dev[0]
    ops = self_seconds(ivs0)
    modules = {}
    mod_line = line_named(planes[0], MODULES_LINE)
    for s, e, name in (intervals(mod_line) if mod_line is not None else []):
        n, t = modules.get(name, (0, 0.0))
        modules[name] = [n + 1, t + (e - s)]
    busy0 = union(ivs0)
    lo, hi = busy0[0][0], busy0[-1][1]
    gaps = name_gaps(busy0, host_spans(pd), lo, hi)
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])
    own = {k: v[1] for k, v in ops.items()}
    collective_s = union_seconds(
        [iv for iv in ivs0 if base_name(iv[2]).startswith(COLLECTIVES)])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": float(window_s),
        "busy_s_by_device": busy,
        "span_s": hi - lo,
        "collective_s": collective_s,
        "ops": ops,
        "modules": modules,
        "device_ops": top(own)[:10],
        "idle_gaps": top(gaps)[:10],
    }


def describe(pd, limit: int = 12) -> str:
    """Planes, lines and the commonest event names: what to read by hand
    before trusting a reduction on a new kind of trace."""
    rows = []
    for p in pd.planes:
        rows.append(f"plane {p.name!r}")
        for ln in p.lines:
            evs = list(ln.events)
            names = {}
            for e in evs:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            top = sorted(names.items(), key=lambda kv: -kv[1])[:limit]
            rows.append(f"  line {ln.name!r}: {len(evs)} events; " + ", ".join(
                f"{n[:60]}={d / 1e6:.2f}ms" for n, d in top))
    return "\n".join(rows)
