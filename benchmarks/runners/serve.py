"""The serve runner: this process holds the chip and runs the program's own
server (``tools/serve.py``: ``_build_engine_and_params`` + ``Server`` on an
ephemeral port, the recipe ``chip_smoke.py`` proved on the chip) with
``InferenceConfig``'s shipped defaults; the configuration sets ``slots`` and
``max_seq_len`` and nothing else. The load generator is a child process
that speaks HTTP.

Set-up: weights drawn on the device from ``--seed``; prefill of one seeded
prompt (32 tokens, or the mix's ``check_prompt_len``: past ``prefill_chunk``
it goes in chunks, as the batcher sends a long prompt) and four decode steps
through the cache against the reference's full forward, on logits; the
server; warm-up requests for every prefill shape
the mix can ask for (each bucket, and the chunked path when prompts pass
``prefill_chunk``), one at a time and then all at once.
Then the child offers the load: the mix's ``lead_in_seconds`` first (still
set-up), then the window, in which this process only scrapes ``GET
/metrics`` at its start and end and (``--trace 1``) traces a few seconds
from a third of it on.

``--trace 2`` leaves that window as ``--trace 0`` has it and traces a tail:
once the window's child has drained and written its record, a second child
offers the same mix for its own lead-in plus the mix's ``trace_seconds``,
and the traced stretch is those last seconds. Either way the capture is
the server's own ``ProfileCapture`` (``server.front.profiler``, the object
``POST /profilez`` starts), opened and closed from this process.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from benchmarks import common, loadgen

# Serving logits, as chip_smoke.py states it: prefill/decode through the KV
# cache in bf16 against the float32 reference over the same weights, compared
# in absolute terms against the logits' own scale (max |logit|): bf16 keeps 8
# bits, L layers of rounding in other orders land within 3 % of that scale
# (chip_smoke read 1.6e-2 on values to 4.1 against a bf16 oracle), and the
# argmax must agree wherever the reference's top-2 margin passes twice the
# tolerance. A float32 engine (the rehearsal) is held to 1e-3.
TOL_LOGITS_REL = {"bfloat16": 3e-2, "float32": 1e-3}
CHECK_PROMPT_LEN = 32
CHECK_DECODE_STEPS = 4


def config_dict(ctx: dict) -> dict:
    return {
        "distributed": {"dp_size": 1, "pp_size": 1, "cp_size": 1,
                        "tp_size": 1, "use_cpu": ctx["rehearse"]},
        "model": ctx["model"],
        "training": {"seq_length": ctx["config"]["serve"]["max_seq_len"],
                     "seed": ctx["seed31"]},
        "dataset": {"name": "synthetic"},
    }


def program_logits(engine, params, prompt, follow=None) -> tuple:
    """(tokens, logits): the prompt through the engine's prefill (past
    ``prefill_chunk`` in chunks written straight into the slot, as the
    batcher admits a long prompt) and ``CHECK_DECODE_STEPS`` decode steps
    through the cache; the last prompt position's logits and each step's.
    Each step feeds the argmax of the last, or ``follow``'s next token (a
    control is read along the tokens the sound program chose)."""
    import jax

    seq = list(prompt)
    if len(prompt) > engine.prefill_chunk:
        cache, last = engine.prefill_chunked(params, engine.init_cache(),
                                             prompt, 0)
    else:
        kv, last = engine.prefill(params, prompt)
        cache = engine.insert(engine.init_cache(), kv, 0, len(prompt))
        del kv
    got = [np.asarray(last, np.float32)[0]]
    slots = engine.slots
    for i in range(CHECK_DECODE_STEPS):
        seq.append(int(follow[i]) if follow is not None
                   else int(np.argmax(got[-1])))
        toks = np.zeros(slots, np.int32)
        toks[0] = seq[-1]
        cache, _, logits = engine.decode_step(
            params, cache, toks, jax.random.PRNGKey(0),
            np.zeros(slots, np.float32), np.zeros(slots, np.int32),
            np.ones(slots, np.float32))
        got.append(np.asarray(logits, np.float32)[0])
    return seq, got


def compare_logits(got, want, tol: float) -> tuple:
    """(ok, rows of (what, err, scale, margin, ok)): max |err| within
    ``tol`` of max |logit|, and the argmax equal where the reference's
    top-2 margin passes twice that."""
    rows, all_ok = [], True
    for i, (g, ref) in enumerate(zip(got, want)):
        scale = float(np.max(np.abs(ref)))
        err = float(np.max(np.abs(g - ref)))
        top2 = np.sort(ref)[-2:]
        margin = float(top2[1] - top2[0])
        ok = err <= tol * scale
        if margin > 2 * tol * scale:
            ok = ok and int(np.argmax(g)) == int(np.argmax(ref))
        rows.append(("prefill" if i == 0 else f"decode+{i}", err, scale,
                     margin, ok))
        all_ok = all_ok and ok
    return all_ok, rows


def reference_logits(ctx, params, seq, n_prompt: int):
    """The reference's logits at the positions ``program_logits`` reads."""
    import jax

    return ctx["reference"].forward_logits(
        params, np.asarray([seq], np.int32), ctx["config"],
        jax.devices()[0])[0][n_prompt - 1:]


def logits_check(ctx, engine, params, prompt) -> tuple:
    """(ok, rows of (what, err, scale, margin, ok))."""
    seq, got = program_logits(engine, params, prompt)
    want = reference_logits(ctx, params, seq, len(prompt))
    return compare_logits(got, want, TOL_LOGITS_REL[
        ctx["config"].get("torch_dtype", "bfloat16")])


def check_prompt(ctx, vocab: int, rng) -> list:
    """The check's seeded prompt: 32 tokens, or the mix's
    ``check_prompt_len``."""
    return [int(t) for t in rng.integers(1, vocab, int(
        ctx["traffic"].get("check_prompt_len", CHECK_PROMPT_LEN)))]


def build_engine(ctx: dict) -> tuple:
    """(cfg, engine, params, registry) as ``tools/serve.py`` builds them
    from the configuration this writes: ``InferenceConfig``'s defaults,
    the cell's slots and window, weights drawn on the device from the
    seed."""
    from picotron_tpu.tools import serve

    config = ctx["config"]
    cfg_path = os.path.join(ctx["scratch"],
                            ctx["cell"]["name"] + ".config.json")
    with open(cfg_path, "w") as f:
        json.dump(config_dict(ctx), f, indent=1)
    args = argparse.Namespace(
        smoke=False, config=cfg_path, load_path="", hf_path="",
        random_init=True, seed=ctx["seed31"],
        slots=config["serve"]["slots"],
        max_seq_len=config["serve"]["max_seq_len"], kv_layout=None,
        role=None, overlap=False, tenant_manifest="")
    return serve._build_engine_and_params(args)


def warm_lengths(traffic: dict, engine) -> list:
    """One prompt length for every prefill shape the mix can reach."""
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    chunk = engine.prefill_chunk
    lengths = {}
    for n in range(lo, min(hi, chunk) + 1):
        lengths.setdefault(engine.prefill_bucket(n), n)
    out = sorted(lengths.values())
    if hi > chunk:
        out.append(hi)  # the chunked path: one shape whatever the length
    return out


def get_text(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return r.read().decode()


def start_load(ctx: dict, port: int, vocab: int, seconds: float,
               out_path: str, seed: int | None = None):
    """A load-generator child, started and told to go: ``lead_in_seconds``
    of the mix and then ``seconds`` more, its record written to
    ``out_path`` when its requests have ended."""
    if os.path.exists(out_path):
        os.unlink(out_path)
    child = subprocess.Popen(
        [sys.executable, os.path.join(os.path.dirname(loadgen.__file__),
                                      "loadgen.py"),
         "--port", str(port), "--traffic", json.dumps(ctx["traffic"]),
         "--seed", str(ctx["seed"] if seed is None else seed),
         "--seconds", str(seconds),
         "--vocab", str(vocab), "--out", out_path],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    if child.stdout.readline().strip() != "READY":
        child.kill()
        child.wait()
        raise SystemExit("[serve] the load generator did not start")
    child.stdin.write("GO\n")
    child.stdin.flush()
    return child


def end_load(ctx: dict, child, out_path: str) -> dict:
    """Wait for the requests in flight to end and the child to write its
    record and exit; the record, every request marked ``ok`` or not."""
    try:
        rc = child.wait(
            timeout=float(ctx["traffic"].get("drain_seconds", 60)) + 30)
    except subprocess.TimeoutExpired:
        child.kill()
        child.wait()
        raise SystemExit("[serve] the load generator overran its time")
    if rc != 0:
        raise SystemExit(f"[serve] the load generator exited {rc}")
    with open(out_path) as f:
        load = json.load(f)
    for r in load["requests"]:
        r["ok"] = loadgen.request_ok(r)
    return load


def traced_tail(ctx: dict, server, vocab: int) -> tuple:
    """(trace record, the tail's load record): after the window, the same
    mix again from a second child under the next seed (the same shapes,
    other tokens), its last ``trace_seconds`` under the server's own
    capture. A longer span for the window's child would be
    another window: an open loop scales its gaps to the span, and the
    requests in flight at the window's end would finish under load."""
    traffic = ctx["traffic"]
    seconds = float(traffic.get("trace_seconds", 3))
    tracer = common.Tracer(ctx)
    capture = server.front.profiler
    tracer.warm(capture)  # on the idle server, before the tail's load
    out_path = os.path.join(ctx["scratch"],
                            ctx["cell"]["name"] + ".tail.json")
    # under the next seed: the window's prompts do not come again
    child = start_load(ctx, server.port, vocab, seconds, out_path,
                       seed=ctx["seed"] + 1)
    try:
        time.sleep(float(traffic.get("lead_in_seconds", 0)))
        tracer.open(capture)
        time.sleep(seconds)
        stopped = capture.stop()
        load = end_load(ctx, child, out_path)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    os.unlink(out_path)
    return tracer.reduce(stopped), load


def run(ctx: dict) -> dict:
    from picotron_tpu.tools import serve

    log, traffic = ctx["log"], ctx["traffic"]
    compiles = common.CompileCounter()
    name = ctx["cell"]["name"]
    cfg, engine, params, registry = build_engine(ctx)
    log(f"[serve] engine and weights after "
        f"{time.perf_counter() - ctx['t0']:.1f} s; attend_impl "
        f"{engine.attend_impl}, decode_block_len {engine.decode_block_len}, "
        f"prefill_chunk {engine.prefill_chunk}")
    vocab = cfg.model.vocab_size
    rng = np.random.default_rng(ctx["seed31"])
    # before the server owns a cache: the chip never holds two caches
    logits_ok, rows = logits_check(ctx, engine, params,
                                   check_prompt(ctx, vocab, rng))
    for what, err, scale, margin, ok in rows:
        log(f"[serve] logits {what}: max|err| {err:.4f} vs max|logit| "
            f"{scale:.3f}, top-2 margin {margin:.4f} "
            f"{'ok' if ok else 'FAIL'}")
    gc.collect()

    server = serve.Server(engine, params, port=0, seed=ctx["seed31"],
                          tenants=registry)
    server.start()
    child = None
    try:
        port = server.port
        # warm-up, twice over every prefill shape, more than one block of
        # tokens each: first one request at a time (a dispatch that compiles
        # holds the front end's lock, and a request posted meanwhile is shed
        # after 10 s), then all at once so the decode program runs full
        lengths = warm_lengths(traffic, engine)
        n_new = 2 * engine.decode_block_len + 1
        t_warm = time.perf_counter()
        recs = []

        def warm(n):
            prompt = [int(t) for t in rng.integers(1, vocab, n)]
            recs.append(loadgen.one_request(port, prompt, n_new,
                                            timeout=1500))

        for n in lengths:
            warm(n)
        threads = [threading.Thread(target=warm, args=(n,)) for n in lengths]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        bad = [r for r in recs if not loadgen.request_ok(r)]
        if bad or len(recs) != 2 * len(lengths):
            raise SystemExit(f"[serve] warm-up request failed: {bad[:1]}")
        log(f"[serve] warmed prompt lengths {lengths} twice in "
            f"{time.perf_counter() - t_warm:.1f} s")

        out_path = os.path.join(ctx["scratch"], name + ".load.json")
        child = start_load(ctx, port, vocab, ctx["seconds"], out_path)
        # the lead-in is the last of the set-up: the window opens on a
        # server in its steady state
        time.sleep(float(traffic.get("lead_in_seconds", 0)))
        before = get_text(port, "/metrics")
        compiles.mark()
        t_begin = time.perf_counter()
        setup_s = t_begin - ctx["t0"]
        trace = None
        if ctx["trace"] == 1:
            tracer = common.Tracer(ctx)
            time.sleep(ctx["seconds"] / 3)
            tracer.open(server.front.profiler)
            time.sleep(min(float(traffic.get("trace_seconds", 3)),
                           ctx["seconds"] / 3))
            stopped = server.front.profiler.stop()
        time.sleep(max(0.0, t_begin + ctx["seconds"] - time.perf_counter()))
        after = get_text(port, "/metrics")
        in_window = compiles.in_window
        # the requests in flight end, then the child writes and exits
        load = end_load(ctx, child, out_path)
        if ctx["debug_dir"]:
            os.replace(out_path, os.path.join(
                ctx["debug_dir"], f"{name}.{ctx['seed']}.load.json"))
        else:
            os.unlink(out_path)
        tail = None
        if ctx["trace"] == 1:
            trace = tracer.reduce(stopped)
        elif ctx["trace"] == 2:
            trace, tail = traced_tail(ctx, server, vocab)
        in_tail = compiles.in_window - in_window
    finally:
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        server.drain_and_join(timeout=60)
    reqs = load["requests"]
    failed = sum(not r["ok"] for r in reqs) + load["unfinished"]
    notes = []
    if not logits_ok:
        notes.append("engine logits disagree with the reference")
    if failed:
        first_bad = next((r for r in reqs if not r["ok"]), None)
        notes.append(f"{failed} requests failed or never ended; first: "
                     f"{ {k: v for k, v in (first_bad or {}).items() if k != 'token_times'} }")
    if in_window:
        notes.append(f"{in_window} compiles inside the window")
    if server.front.dead:
        notes.append("the dispatch loop died")
    n_tok = sum(len(r["token_times"]) for r in reqs)
    t_end = load["t0"] + load["seconds"]
    # sent, and not yet streaming when the window closed: the backlog
    queued = sum(r["sent"] <= t_end and (not r["token_times"]
                                         or r["token_times"][0] > t_end)
                 for r in reqs) + load["unfinished"]
    log(f"[serve] {load['sent']} requests sent, {len(reqs)} ended, {failed} "
        f"failed; {n_tok} tokens streamed; {queued} waiting for a first "
        f"token at the window's end")
    tail_ok = True
    if tail is not None:
        # the tail's requests were live during the traced stretch, so they
        # go where ``stats.live_tokens`` looks; every window reader filters
        # on [t0, t0 + seconds], which they lie outside of. One that failed
        # fails the run's ``correct``, not the window's ``failed`` share.
        tail_bad = (sum(not r["ok"] for r in tail["requests"])
                    + tail["unfinished"])
        tail_ok = not tail_bad and not in_tail
        if not tail_ok:
            notes.append(f"traced tail: {tail_bad} requests failed or never "
                         f"ended, {in_tail} compiles")
        log(f"[serve] traced tail: {tail['sent']} requests sent, "
            f"{len(tail['requests'])} ended, {tail_bad} failed")
        reqs.extend(tail["requests"])
    return {
        "setup_s": setup_s,
        "window_s": load["seconds"],
        "load": load,
        "queued_at_end": queued,
        "slots": engine.slots,
        "decode_block_len": engine.decode_block_len,
        "metrics_before": before, "metrics_after": after,
        "attempted": load["sent"],
        "failed": failed,
        "correct": logits_ok and not failed and not in_window
        and not server.front.dead and tail_ok,
        "compiles_in_window": in_window,
        "trace": trace,
        "notes": notes,
    }
