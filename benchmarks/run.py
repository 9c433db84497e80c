#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1|2>

Everything that belongs to one cell is data found by name: the cell's
configuration (``benchmarks/configs/<config>.json``, which may list further
``model_keys`` for the program and name its ``reference``), its traffic mix
(``benchmarks/traffic/<traffic>.json``, whose ``runner`` names a module of
``benchmarks/runners/``), and one reader per metric
(``benchmarks/end_to_end/<name>.py``, ``benchmarks/layer_metrics/<name>.py``,
each ``read(run) -> float | None``). Adding a cell, a configuration, a mix or
a metric adds files and manifest entries and edits nothing here.

The run measures on the accelerator JAX finds, and refuses (exit 2, no
result line) when that is a CPU or holds fewer chips than the cell asks
for. ``--rehearse`` is the one way onto the CPU: it pins the CPU platform
before JAX loads, shrinks model and traffic to the ``rehearsal`` sizes in
the data files, and prints no number under a metric's name, so a rehearsal
can never pass for a chip run.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when a trace was
taken). With ``--trace 0`` the metrics are the cell's end-to-end metrics,
measured with the profiler off. ``--trace 2`` is that very run followed by
a short traced tail of the same traffic: the window, its ``setup_s``,
``correct``, ``attempted``, ``failed`` and end-to-end numbers are a ``--trace
0`` run's, and only when the window has closed does the runner open the
program's own capture (``picotron_tpu.obs.ProfileCapture``: the program
captures, the benchmark reduces), once to throw away and once for a few
seconds or steps; the line then holds the end-to-end and the per-layer
metrics side by side, and ``device`` gains ``busy_s`` and ``window_s`` of
the traced stretch. ``--trace 1`` is the older run of its own that traces
from the middle of its window and prints the per-layer metrics alone.
Everything else goes to stderr.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
SCRATCH = os.path.join(ROOT, ".bench_scratch")  # configs, traces; gitignored
SEED_MOD = 2**31 - 1  # the driver's seeds pass 32 signed bits; JAX keys do not


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json; it has "
                     f"{[w['name'] for w in manifest['workloads']]}")


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, one level into nested dicts."""
    out = dict(base)
    for k, v in over.items():
        out[k] = {**out[k], **v} if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def apply_sets(traffic: dict, sets) -> dict:
    """``--set key=value`` on the traffic mix: how the rate sweep is made
    with this very command. Values are JSON; keys may be dotted."""
    for item in sets or ():
        key, _, raw = item.partition("=")
        node = traffic
        *path, leaf = key.split(".")
        for p in path:
            node = node[p]
        node[leaf] = json.loads(raw)
    return traffic


def load_reader(kind: str, name: str):
    from benchmarks import common

    return common.load_file(kind, name).read


def read_metrics(manifest: dict, kind: str, cell: str, run: dict) -> dict:
    """{name: {"value", "unit"}} for every metric of ``kind`` this cell
    reports. A reader that finds nothing returns None and is left out."""
    out = {}
    for m in manifest[kind]:
        if cell not in m.get("workloads", (cell,)):
            continue
        value = load_reader(kind if kind == "end_to_end" else "layer_metrics",
                            m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def device_record(jax) -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(jax, n: int) -> int:
    """Peak on the fullest of the ``n`` chips used, as the backend has it."""
    peak = 0
    for d in jax.devices()[:n]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use",
                                       stats.get("bytes_in_use", 0))))
    return peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU; prints no metric value")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=JSON", help="override a traffic parameter "
                    "(the rate sweep); the result is not the cell's")
    args = ap.parse_args(argv)

    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find_cell(manifest, args.workload)
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(
        HERE, "traffic", cell["traffic"] + ".json"))
    chips = int(cell["chips"])

    if args.rehearse:
        # before JAX loads: the CPU, with as many virtual devices as chips
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips} "
            + os.environ.get("XLA_FLAGS", ""))
        config = merged(config, config.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))
    traffic = apply_sets(traffic, args.set)

    sys.path.insert(0, ROOT)
    from benchmarks import common

    # a configuration that asks for what this program or this benchmark
    # lacks ends here (exit 2), before the backend is touched
    model = common.model_section(config)
    reference = common.load_reference(config)

    import jax

    from picotron_tpu.utils import enable_compile_cache

    dev = device_record(jax)
    log(f"device: {json.dumps(dev)}")
    if args.rehearse:
        if dev["platform"] != "cpu":
            raise SystemExit("run.py: --rehearse must run on the CPU")
        log("REHEARSAL on the CPU at toy size: no metric value is printed")
    elif dev["platform"] == "cpu":
        log("run.py: JAX found no accelerator; refusing to measure "
            "(--rehearse is the CPU control-flow rehearsal)")
        return 2
    if dev["count"] < chips:
        log(f"run.py: cell {cell['name']} needs {chips} chips, JAX has "
            f"{dev['count']}")
        return 2
    # one fixed directory inside the checkout (or JAX_COMPILATION_CACHE_DIR);
    # cache every program, however quick its compile, so that only the first
    # run of a cell in a checkout compiles
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    os.makedirs(SCRATCH, exist_ok=True)
    # BENCH_DEBUG_DIR: where to leave what a hand reads (the trace's planes
    # and lines, the generator's per-request record); unset in a check
    debug_dir = os.environ.get("BENCH_DEBUG_DIR")
    if debug_dir:
        os.makedirs(debug_dir, exist_ok=True)
    ctx = {
        "t0": T0, "root": ROOT, "scratch": SCRATCH, "cell": cell,
        "config": config, "traffic": traffic, "chips": chips,
        "model": model, "reference": reference,
        "seed": args.seed, "seed31": args.seed % SEED_MOD,
        "seconds": args.seconds, "trace": args.trace,
        "rehearse": args.rehearse, "device": dev, "log": log,
        "debug_dir": debug_dir,
    }
    runner = importlib.import_module(f"benchmarks.runners.{traffic['runner']}")
    run = runner.run(ctx)
    run.update(cell=cell, config=config, traffic=traffic, chips=chips,
               device=dev, rehearse=args.rehearse)
    if not args.rehearse:
        from benchmarks import opcount

        run["peaks"] = opcount.peaks(dev["kind"])  # unknown kind: an error

    metrics = {}
    for kind in (("end_to_end",), ("per_layer",),
                 ("end_to_end", "per_layer"))[args.trace]:
        metrics.update(read_metrics(manifest, kind, cell["name"], run))
    device = dict(dev, memory_peak_bytes=memory_peak_bytes(jax, chips))
    out = {"correct": bool(run["correct"]), "attempted": int(run["attempted"]),
           "failed": int(run["failed"]), "metrics": metrics, "device": device,
           "compiles_in_window": int(run.get("compiles_in_window", 0))}
    trace = run.get("trace")
    if args.trace and trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"][:10],
                            "idle_gaps": trace["idle_gaps"][:10]}
    if args.set:
        out["overrides"] = args.set  # a sweep's point, not the cell's
    if args.rehearse:
        # the control flow is proven by which readers found something; their
        # CPU numbers are not device metrics and are not printed
        out["rehearsal"] = True
        out["computed"] = sorted(metrics)
        out["metrics"] = {}
        out.pop("breakdown", None)
        for k in ("busy_s", "window_s"):
            device.pop(k, None)
    for note in run.get("notes", ()):
        log("note:", note)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
