"""Decode throughput benchmark: continuous-batched KV-cache generation.

The serving-side complement of bench.py's training MFU: with every engine
slot busy, how many tokens/sec does the decode hot path sustain?
Protocol: prefill fills all slots with fixed-length random prompts, a
warmup call absorbs compilation, then the timed window runs end-to-end
(including the host round-trip that feeds sampled tokens back — that
latency is part of serving).

Three modes, selected by ``--block-len`` / ``--spec-len``:

- ``--block-len 1`` (default): the classic per-token loop — one
  ``decode_step`` dispatch, one host sync, per generated token
  (dispatches/token = 1.0);
- ``--block-len N``: the blocked fast path — ``decode_block`` runs N
  autoregressive steps inside one jitted program with on-device stop
  state, so the host syncs once per N tokens (dispatches/token = 1/N).
  The tokens/s delta between the two modes IS the host-dispatch overhead
  the block amortizes.
- ``--spec-len G``: speculative decoding — prompts are REPETITIVE (the
  regime prompt-lookup drafting serves: boilerplate, code, loops), the
  n-gram drafter proposes G tokens per slot per round, and one
  ``engine.verify`` dispatch accepts the matching prefix. Same protocol
  and normalization as the other modes (prefill outside the timed
  window, dispatches per PER-SLOT decode token), so zero acceptance
  reads exactly 1.0 — the per-token baseline — and every accepted draft
  pushes dispatches/token strictly below it (~1/(1 + r*G) at
  accept-rate r).

Prints ONE JSON line starting ``{"metric"`` (the bench_record contract):
tokens/s/chip on SmolLM-1.7B on a TPU, with ``dispatches_per_token`` (and
``accept_rate`` when speculating) riding along so the host-sync win is
visible in the bench trajectory. Every record names the device it ran on.
The script measures in THIS process and fails where it stands: without a
chip it refuses to run unless the caller pinned ``JAX_PLATFORMS=cpu`` — the
explicit opt-in of the ``make *-smoke`` targets, which drive a tiny model
through the same code for its counts and gates (their metric names end in
``_cpu_smoke``; a CPU timing is never a device number).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# --dp N shards ONE engine's slot axis over N dp shards; on the CPU proxy
# that needs a forced multi-device host platform, and XLA fixes the device
# count at backend init — so the flag must land BEFORE any jax import
# (picotron_tpu's package import below touches jax via topology).
if "--dp" in sys.argv:
    try:
        _dp = int(sys.argv[sys.argv.index("--dp") + 1])
    except (IndexError, ValueError):
        _dp = 1
    if (_dp > 1 and "xla_force_host_platform_device_count"
            not in os.environ.get("XLA_FLAGS", "")):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={max(8, _dp)}"
        ).strip()

from picotron_tpu.bench_record import BENCH_METRICS
from picotron_tpu.utils import (device_record, enable_compile_cache,
                                require_accelerator)

# verify-dispatch rounds absorbed before the spec mode's timed window —
# shared by run_spec and main's cache-budget sizing
SPEC_WARMUP_ROUNDS = 4


def logits_bytes_to_host_per_token(engine, vocab: int, block_len: int,
                                   spec_len: int = 0) -> int:
    """Bytes of sampling payload that cross the device->host boundary per
    generated token: the [B, V] fp32 logits the per-token loop round-trips
    just to pick one id each — or, everywhere sampling is fused into the
    dispatch (``--sample-on-device``, blocked decode's on-device stop
    state, the speculative verify), the int32 token ids alone. The
    acceptance shape: V*4 per token on the host-sampling per-token loop,
    O(B) per dispatch (= 4 bytes per token) with the epilogue on."""
    if block_len == 1 and spec_len == 0 and not engine.sample_on_device:
        return vocab * 4 + 4  # [V] fp32 logits + the sampled id fed back
    if spec_len > 0:
        # one verify dispatch emits ~(1 + r*G) ids per slot; conservatively
        # charge the whole emitted row (G+1 ids) per produced token
        return (spec_len + 1) * 4
    return 4  # token ids only — logits never leave the device


def dispatch_latency_summary(engine) -> dict:
    """Per-kind dispatch-latency percentiles out of the registry histogram
    PR 10 wired (``picotron_dispatch_seconds``): the per-rung before/after
    the bench JSON records, so an A/B across flag flips (serial vs
    pipelined DMA, host vs on-device sampling, uniform vs hot_bf16 pages)
    is a diff of two JSON lines, not a re-instrumentation."""
    out = {}
    for kind in ("decode", "verify"):
        h = engine.obs.registry.histogram(
            "picotron_dispatch_seconds",
            "dispatch wall time incl. host sync, by kind", kind=kind)
        p = h.percentiles()
        if p is not None:
            out[kind] = p
    return out


def kv_bytes_per_token(engine, lengths) -> int:
    """Estimated KV HBM bytes the attend moves per cache walk: layers x
    K+V x (attention window rows) x kv_heads x head_dim x storage bytes,
    plus the per-row fp32 scale vectors for int8 caches. The window is what
    distinguishes the kernels — the dense attend walks the full
    ``max_seq_len`` cache block, the flash kernel only the live rows
    (``lengths``, averaged over slots at the end of the timed window). The
    dense int8 path additionally materializes whole-window dequantized
    fp32 copies of K and V (kv_cache.attend) — that write+read traffic is
    counted, since hiding it would make dense-int8 look CHEAPER than
    dense-bf16, the opposite of what the flash path exists to fix. One
    walk serves one decode token (decode/blocked modes); speculative
    callers scale by dispatches-per-token (one walk per verify dispatch
    emits ~1/dpt tokens).

    Paged layout (``--kv-layout paged``): flash walks whole pages, so the
    live window rounds up to the page size; dense first GATHERS the
    slot's pages into a contiguous full-window copy (paged_kv.attend) —
    that copy's write+read is counted on top, the same honesty rule as
    the dense-int8 materialization."""
    import numpy as np

    m = engine.cfg.model
    live = float(np.mean(np.asarray(lengths)))
    paged = engine.paged is not None
    if engine.attend_impl == "flash":
        window = (-(-live // engine.page_len) * engine.page_len if paged
                  else live)
    else:
        window = float(engine.max_seq_len)
    fp_row = 2 * m.num_key_value_heads * m.head_dim * \
        engine.cache_dtype.itemsize
    q_row = (2 * m.num_key_value_heads * m.head_dim  # int8 bytes
             + 2 * m.num_key_value_heads * 4)  # + per-row fp32 scales
    if getattr(engine, "page_policy", False):
        # hot_bf16 mixed pages: the flash DMA fetches each page from ONE
        # representation — full precision for hot (shared) pages, int8 +
        # scales for cold (exclusive) tails — so per-row bytes are the
        # live-page mix. The dense reference gathers BOTH windows plus
        # the fp32 select copy (write + read), the same honesty rule as
        # the dense-int8 materialization below.
        flags = engine.paged.quant_flags()
        refs = engine.paged.pool.refs
        live = np.flatnonzero(refs[1:] > 0) + 1
        qfrac = float(np.mean(flags[live])) if live.size else 0.0
        if engine.attend_impl == "flash":
            per_row = qfrac * q_row + (1.0 - qfrac) * fp_row
        else:
            per_row = (fp_row + q_row
                       + 2 * m.num_key_value_heads * m.head_dim * 4 * 2)
    else:
        per_row = fp_row
        if engine.quantized:
            per_row += 2 * m.num_key_value_heads * 4  # k_scale/v_scale rows
            if engine.attend_impl == "dense":
                # whole-window fp32 K/V materialization: 4 bytes written
                # then read back per element, on top of the int8 cache read
                per_row += 2 * m.num_key_value_heads * m.head_dim * 4 * 2
    if paged and engine.attend_impl == "dense":
        # the gathered contiguous window copy: written then read back at
        # the storage width (the fp32 materialization above already
        # covers the int8 dequant copy)
        per_row += 2 * m.num_key_value_heads * m.head_dim * \
            engine.cache_dtype.itemsize * 2
    return int(round(m.num_hidden_layers * window * per_row))


def bench_params(engine, cfg):
    """Seed-derived weights in the engine's storage format (int8 engines
    get the per-channel quantized tree), plus their total byte footprint
    — the ``weight_bytes_total`` the int8 mode roughly halves."""
    import jax

    from picotron_tpu.models import llama

    params = jax.jit(lambda k: llama.init_params(k, cfg.model))(
        jax.random.PRNGKey(0))
    if engine.quant_weights:
        params = llama.quantize_params(params)
    params = engine.shard_params(params)
    return params, llama.param_bytes(params)


def run(cfg, *, slots: int, max_seq_len: int, prompt_len: int,
        steps: int, warmup: int = 8, block_len: int = 1,
        attend_impl: str = "dense", kv_layout: str = "contiguous",
        kv_page_policy: str = "uniform", sample_on_device: bool = False,
        weight_dtype: str = "bf16"):
    """Time ``steps`` decode rounds (tokens per slot). Returns
    (tokens/s, dispatches_per_token, kv_bytes/token, weight_bytes_total,
    engine)."""
    import jax
    import numpy as np

    from picotron_tpu.inference import InferenceEngine

    engine = InferenceEngine(cfg, slots=slots, max_seq_len=max_seq_len,
                             decode_block_len=block_len,
                             attend_impl=attend_impl, kv_layout=kv_layout,
                             kv_page_policy=kv_page_policy,
                             sample_on_device=sample_on_device,
                             weight_dtype=weight_dtype)
    params, weight_bytes = bench_params(engine, cfg)
    cache = engine.init_cache()
    rng = np.random.default_rng(0)
    # greedy prefill epilogue (temp 0) == the host argmax it replaces
    pf_sample = ((jax.random.PRNGKey(1), 0.0, 0, 1.0)
                 if sample_on_device else None)
    for s in range(slots):
        prompt = rng.integers(1, cfg.model.vocab_size, prompt_len)
        kv, _ = engine.prefill(params, prompt, sample=pf_sample)
        cache = engine.insert(cache, kv, s, prompt_len)

    toks = np.ones(slots, np.int32)
    temp = np.zeros(slots, np.float32)  # greedy: no sampling noise in the timing
    top_k = np.zeros(slots, np.int32)
    top_p = np.ones(slots, np.float32)
    key = jax.random.PRNGKey(0)

    assert steps % block_len == 0, "steps must divide into whole blocks"
    assert prompt_len + warmup * block_len + steps <= max_seq_len, \
        "cache would overflow"

    if block_len == 1:
        for _ in range(warmup):
            key, sub = jax.random.split(key)
            cache, toks, _ = engine.decode_step(params, cache, toks, sub,
                                                temp, top_k, top_p)
        jax.block_until_ready(toks)
        t0 = time.perf_counter()
        for _ in range(steps):
            key, sub = jax.random.split(key)
            td = time.perf_counter()
            cache, toks, _ = engine.decode_step(params, cache, toks, sub,
                                                temp, top_k, top_p)
            toks = np.asarray(toks)  # the host feedback every real server pays
            # per-dispatch wall (incl. the sync above) into the registry
            # histogram the JSON record snapshots
            engine.observe_dispatch("decode", time.perf_counter() - td)
        dt = time.perf_counter() - t0
        dispatches = steps
        last = toks
    else:
        eos = np.full(slots, -1, np.int32)  # bench streams never stop early

        def block(cache, toks, key):
            subs = []
            for _ in range(block_len):
                key, sub = jax.random.split(key)
                subs.append(np.asarray(sub))
            budget = np.full(slots, block_len, np.int32)
            td = time.perf_counter()
            r = engine.decode_block(
                params, cache, toks, np.stack(subs), eos, budget,
                temp, top_k, top_p)
            cache, out, counts = r.cache, r.tokens, r.counts
            out = np.asarray(out)  # one host sync per block, not per token
            engine.observe_dispatch("decode", time.perf_counter() - td)
            assert np.all(np.asarray(counts) == block_len)
            return cache, out[:, -1], key

        for _ in range(warmup):
            cache, toks, key = block(cache, toks, key)
        t0 = time.perf_counter()
        for _ in range(steps // block_len):
            cache, toks, key = block(cache, toks, key)
        dt = time.perf_counter() - t0
        dispatches = steps // block_len
        last = toks

    assert np.all((last >= 0) & (last < cfg.model.vocab_size))
    kv_bytes = kv_bytes_per_token(engine, cache["lengths"])
    return slots * steps / dt, dispatches / steps, kv_bytes, weight_bytes, \
        engine


def run_spec(cfg, *, slots: int, max_seq_len: int, prompt_len: int,
             steps: int, warmup_rounds: int = SPEC_WARMUP_ROUNDS,
             spec_len: int = 4, attend_impl: str = "dense",
             kv_layout: str = "contiguous",
             kv_page_policy: str = "uniform",
             sample_on_device: bool = False,
             weight_dtype: str = "bf16", drafter: str = "ngram"):
    """Time ``steps`` speculative decode tokens per slot: the same
    protocol as ``run`` — prefill fills every slot OUTSIDE the timed
    window, warmup rounds absorb compilation, then the timed window runs
    draft (host-side n-gram lookup) + one ``engine.verify`` dispatch per
    round until every slot has produced ``steps`` tokens. Prompts are
    REPETITIVE (one shared pattern — the regime prompt-lookup speculation
    serves: greedy decode falls into token loops the drafter rides).

    dispatches-per-token is dispatches / per-slot decode tokens, exactly
    ``run``'s normalization: with nothing accepted every round yields one
    token per slot and dpt == 1.0 (the spec-off per-token baseline);
    every accepted draft pushes it strictly below. Returns (tokens/s,
    dispatches_per_token, accept_rate, kv_bytes/token,
    weight_bytes_total, engine)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from picotron_tpu.inference import (
        InferenceEngine,
        LearnedDrafter,
        NgramDrafter,
    )

    engine = InferenceEngine(cfg, slots=slots, max_seq_len=max_seq_len,
                             spec_len=spec_len, attend_impl=attend_impl,
                             kv_layout=kv_layout,
                             kv_page_policy=kv_page_policy,
                             sample_on_device=sample_on_device,
                             weight_dtype=weight_dtype, drafter=drafter)
    params, weight_bytes = bench_params(engine, cfg)
    rng = np.random.default_rng(0)
    prompt = np.resize(rng.integers(1, cfg.model.vocab_size, 4), prompt_len)
    assert (prompt_len + 1 + warmup_rounds * (spec_len + 1) + steps
            <= max_seq_len), "cache would overflow"

    cache = engine.init_cache()
    toks = np.zeros(slots, np.int32)
    learned = engine.return_hidden  # drafter == "learned"
    hidden = (jnp.zeros((slots, cfg.model.hidden_size),
                        jnp.dtype(cfg.model.dtype)) if learned else None)
    # greedy prefill epilogue (temp 0) == the host argmax it replaces
    pf_sample = ((jax.random.PRNGKey(1), 0.0, 0, 1.0)
                 if sample_on_device else None)
    hist = []
    for s in range(slots):
        out = engine.prefill(params, prompt, sample=pf_sample)
        kv, logits = out[:2]
        if learned:
            hidden = hidden.at[s].set(jnp.asarray(out[2])[0])
        cache = engine.insert(cache, kv, s, prompt_len)
        # epilogue engines return the greedy token id directly
        toks[s] = (np.asarray(logits).reshape(-1)[0] if sample_on_device
                   else np.argmax(np.asarray(logits)[0]))
        hist.append(list(prompt) + [int(toks[s])])
    proposer = (LearnedDrafter(engine, params) if learned
                else NgramDrafter(engine.spec_ngram))

    eos = np.full(slots, -1, np.int32)  # bench streams never stop early
    temp = np.zeros(slots, np.float32)
    top_k = np.zeros(slots, np.int32)
    top_p = np.ones(slots, np.float32)
    key = jax.random.PRNGKey(0)
    produced = np.zeros(slots, np.int64)
    stats = np.zeros(2, np.int64)  # proposed, accepted

    def spec_round(cache, key, budget):
        nonlocal hidden
        import jax.numpy as jnp

        tokens = np.zeros((slots, spec_len + 1), np.int32)
        active = budget > 0
        if learned:
            td = time.perf_counter()
            batch = proposer.propose_batch(toks, hidden, spec_len)
            engine.observe_dispatch("draft", time.perf_counter() - td)
        for s in np.flatnonzero(active):
            tokens[s, 0] = toks[s]
            tokens[s, 1:] = (batch[s] if learned
                             else proposer.propose(hist[s], spec_len))
        key, sub = jax.random.split(key)
        td = time.perf_counter()
        out = engine.verify(
            params, cache, tokens, sub, eos, budget, temp, top_k, top_p)
        cache, emitted, accepted = out.cache, out.tokens, out.accepted
        emitted = np.asarray(emitted)  # ONE host sync per dispatch
        counts = np.asarray(out.counts)
        if learned:
            hidden = jnp.where(jnp.asarray(counts > 0)[:, None], out.hidden,
                               hidden)
        engine.observe_dispatch("verify", time.perf_counter() - td)
        for s in np.flatnonzero(counts):
            hist[s].extend(int(t) for t in emitted[s, : counts[s]])
            toks[s] = emitted[s, counts[s] - 1]
        stats[0] += spec_len * int(active.sum())
        stats[1] += int(np.asarray(accepted).sum())
        return cache, key, counts

    for _ in range(warmup_rounds):
        cache, key, _ = spec_round(
            cache, key, np.full(slots, spec_len + 1, np.int32))
    stats[:] = 0
    dispatches = 0
    t0 = time.perf_counter()
    while np.any(produced < steps):
        cache, key, counts = spec_round(
            cache, key, (steps - produced).astype(np.int32))
        produced += counts
        dispatches += 1
    dt = time.perf_counter() - t0
    accept = stats[1] / max(stats[0], 1)
    # one cache walk per verify dispatch emits ~1/dpt tokens, so per-TOKEN
    # bytes scale by dispatches-per-token (keeps spec rows comparable to
    # the decode modes' one-walk-per-token accounting)
    dpt = dispatches / steps
    kv_bytes = int(round(kv_bytes_per_token(engine, cache["lengths"]) * dpt))
    return slots * steps / dt, dpt, accept, kv_bytes, weight_bytes, engine


def run_spec_auto(cfg, *, slots: int, max_seq_len: int, prompt_len: int,
                  steps: int, spec_len: int = 4, drafter: str = "ngram",
                  attend_impl: str = "dense",
                  kv_layout: str = "contiguous",
                  kv_page_policy: str = "uniform",
                  sample_on_device: bool = False,
                  weight_dtype: str = "bf16"):
    """The CONTROLLER run: a mixed repetitive/random workload through the
    real ContinuousBatcher with ``inference.spec_controller`` enabled.
    Half the requests carry the repetitive prompt ``run_spec`` uses (the
    regime speculation serves — their slots should converge to
    spec_len > 0 and per-request dispatches/token < 1), half carry
    RANDOM prompts (hard traffic — their slots should converge to
    spec_len == 0, speculation out of the way). Greedy, so output is
    bit-identical to spec-off regardless of what the controller decides.

    Returns (tokens/s, dispatches_per_token, accept_rate, kv_bytes/token,
    weight_bytes_total, engine, auto) where ``auto`` carries the
    controller story: spec_len_effective (mean final per-slot draft
    length), accept_rate_by_drafter, controller-decision counts, and
    per-regime dispatches-per-token."""
    import numpy as np

    from picotron_tpu.config import Config
    from picotron_tpu.inference import ContinuousBatcher, InferenceEngine, \
        Request

    raw = cfg.to_dict()
    raw["inference"].update(dict(
        spec_len=spec_len, drafter=drafter,
        spec_controller=dict(raw["inference"].get("spec_controller", {}),
                             enabled=True, window=max(4, spec_len),
                             hysteresis=2)))
    cfg = Config.from_dict(raw)
    import jax

    engine = InferenceEngine(cfg, slots=slots, max_seq_len=max_seq_len,
                             attend_impl=attend_impl, kv_layout=kv_layout,
                             kv_page_policy=kv_page_policy,
                             sample_on_device=sample_on_device,
                             weight_dtype=weight_dtype)
    params, weight_bytes = bench_params(engine, cfg)
    rng = np.random.default_rng(0)
    rep_prompt = [int(t) for t in np.resize(
        rng.integers(1, cfg.model.vocab_size, 4), prompt_len)]
    # warmup: absorb compilation OUTSIDE the timed window, run/run_spec's
    # protocol — a throwaway batcher on the same engine compiles the
    # prefill bucket, the verify program, and (learned) the draft
    # dispatch; the decode_block fallback program (reached mid-run once
    # the controller turns slots off) is compiled explicitly against a
    # scratch cache with zero budgets
    warm = ContinuousBatcher(engine, params)
    warm.run([Request("w_rep", list(rep_prompt),
                      max_new_tokens=spec_len + 2),
              Request("w_rand",
                      [int(t) for t in rng.integers(
                          1, cfg.model.vocab_size, prompt_len)],
                      max_new_tokens=spec_len + 2)])
    keys = np.stack([np.asarray(jax.random.PRNGKey(i))
                     for i in range(engine.decode_block_len)])
    zero = np.zeros(slots, np.int32)
    engine.decode_block(params, engine.init_cache(), zero, keys,
                        np.full(slots, -1, np.int32), zero,
                        np.zeros(slots, np.float32), zero,
                        np.ones(slots, np.float32))
    batcher = ContinuousBatcher(engine, params)
    # registry counters are engine-lifetime: snapshot what the warmup
    # drafted so the per-drafter split below covers the timed run only
    reg = batcher.obs.registry
    base = {kind: (reg.counter("picotron_drafter_proposed_total",
                               drafter=kind).value,
                   reg.counter("picotron_drafter_accepted_total",
                               drafter=kind).value)
            for kind in batcher._drafters}
    reqs = []
    for s in range(slots):
        if s % 2 == 0:
            reqs.append(Request(f"rep{s}", list(rep_prompt),
                                max_new_tokens=steps))
        else:
            prompt = [int(t) for t in
                      rng.integers(1, cfg.model.vocab_size, prompt_len)]
            reqs.append(Request(f"rand{s}", prompt, max_new_tokens=steps))
    t0 = time.perf_counter()
    results = batcher.run(reqs)
    dt = time.perf_counter() - t0
    total_toks = sum(len(r.tokens) for r in results.values())
    dpt = batcher.decode_dispatches / max(total_toks, 1)

    def regime_dpt(prefix):
        rs = [r for u, r in results.items() if u.startswith(prefix)]
        toks = sum(len(r.tokens) for r in rs)
        return round(sum(r.dispatches for r in rs) / max(toks, 1), 4)

    by_drafter = {}
    for kind in batcher._drafters:
        bp, ba = base.get(kind, (0.0, 0.0))
        prop = reg.counter("picotron_drafter_proposed_total",
                           drafter=kind).value - bp
        if prop:
            acc = reg.counter("picotron_drafter_accepted_total",
                              drafter=kind).value - ba
            by_drafter[kind] = round(acc / prop, 4)
    auto = {
        "spec_len_effective": round(float(np.mean(
            [r.spec_len_final or 0 for r in results.values()])), 3),
        "spec_len_by_regime": {
            "repetitive": round(float(np.mean(
                [r.spec_len_final or 0 for u, r in results.items()
                 if u.startswith("rep")])), 3),
            "random": round(float(np.mean(
                [r.spec_len_final or 0 for u, r in results.items()
                 if u.startswith("rand")])), 3)},
        "dispatches_per_token_by_regime": {
            "repetitive": regime_dpt("rep"), "random": regime_dpt("rand")},
        "accept_rate_by_drafter": by_drafter,
        "controller_decisions": batcher.controller.decisions,
    }
    # end-of-stream live window per slot: retired slots have released
    # their cache lengths to 0, so reconstruct what each request held
    # when it finished (run/run_spec sample lengths while still parked)
    final_lengths = np.asarray(
        [len(r.prompt) + len(r.tokens) for r in results.values()],
        np.int64)
    kv_bytes = int(round(kv_bytes_per_token(engine, final_lengths) * dpt))
    return (total_toks / dt, dpt, batcher.accept_rate or 0.0, kv_bytes,
            weight_bytes, engine, auto)


def run_tenants(cfg, *, tenants: int, adapter_rank: int, slots: int,
                max_seq_len: int, prompt_len: int, steps: int,
                spec_len: int = 0, drafter: str = "ngram",
                attend_impl: str = "dense", kv_layout: str = "contiguous",
                kv_page_policy: str = "uniform",
                sample_on_device: bool = False,
                weight_dtype: str = "bf16"):
    """The MULTI-TENANT run (ISSUE 16): ``tenants`` rank-``adapter_rank``
    adapters over one shared base, plus base-only (null-adapter) rows, all
    mixed in the SAME continuous batch — every decode/verify dispatch
    serves several tenants at once through the segmented adapter matmul.
    Requests round-robin across tenants (repetitive prompts, so the
    speculative variant has an attractor to ride) with one anonymous
    base request per batch wave riding along as the isolation control.

    Returns (tokens/s, dispatches_per_token, accept_rate_or_None,
    kv_bytes/token, weight_bytes_total, engine, tenancy) where
    ``tenancy`` carries the per-tenant story: tokens, dispatches/token,
    TTFT, accept (spec runs), and the pack's adapter_bytes_per_token —
    the HBM cost every decode step pays to stream all live adapters."""
    import numpy as np

    from picotron_tpu.inference import ContinuousBatcher, InferenceEngine, \
        Request
    from picotron_tpu.inference import tenancy as _tenancy

    pack = _tenancy.AdapterPack(cfg.model, slots=tenants + 1,
                                rank=adapter_rank)
    for i in range(1, tenants + 1):
        # a visible per-tenant voice: large enough to steer greedy argmax
        # on the tiny smoke model, distinct seed per tenant
        pack.set_slot(i, pack.random_leaves(adapter_rank, seed=i,
                                            scale=0.5))
    engine = InferenceEngine(cfg, slots=slots, max_seq_len=max_seq_len,
                             spec_len=spec_len, attend_impl=attend_impl,
                             kv_layout=kv_layout,
                             kv_page_policy=kv_page_policy,
                             sample_on_device=sample_on_device,
                             weight_dtype=weight_dtype, drafter=drafter,
                             adapters=pack)
    params, weight_bytes = bench_params(engine, cfg)
    rng = np.random.default_rng(0)
    rep_prompt = [int(t) for t in np.resize(
        rng.integers(1, cfg.model.vocab_size, 4), prompt_len)]

    def reqs_for(tag):
        out = []
        for s in range(slots):
            tid = s % (tenants + 1)  # slot 0 of each wave = base-only
            out.append(Request(
                f"{tag}t{tid}_{s}", list(rep_prompt),
                max_new_tokens=steps,
                tenant=f"tenant{tid}" if tid else "",
                adapter_slot=tid,
                priority=2 if tid == 1 else 1,  # one premium class
                ttft_slo_ms=500.0 if tid == 1 else None))
        return out

    # warmup wave absorbs compilation (prefill bucket + decode/verify
    # programs) outside the timed window, run/run_spec's protocol
    warm = ContinuousBatcher(engine, params)
    warm.run([Request(f"w{i}", list(rep_prompt), max_new_tokens=2,
                      adapter_slot=i % (tenants + 1))
              for i in range(min(slots, tenants + 1))])
    batcher = ContinuousBatcher(engine, params)
    t0 = time.perf_counter()
    results = batcher.run(reqs_for("m_"))
    dt = time.perf_counter() - t0
    total_toks = sum(len(r.tokens) for r in results.values())
    dpt = batcher.decode_dispatches / max(total_toks, 1)

    per_tenant = {}
    for tid in range(tenants + 1):
        rs = [r for u, r in results.items()
              if u.startswith(f"m_t{tid}_")]
        toks = sum(len(r.tokens) for r in rs)
        disp = sum(r.dispatches for r in rs)
        ttfts = [r.ttft_s for r in rs if r.ttft_s is not None]
        row = {
            "tokens": toks,
            "dispatches_per_token": round(disp / max(toks, 1), 4),
            "ttft_s": round(float(np.mean(ttfts)), 5) if ttfts else None,
        }
        if spec_len > 0:
            # each verify dispatch emits 1 + accepted and proposes
            # spec_len, so the per-tenant accept rate falls out of the
            # per-request (dispatches, tokens) pair
            row["accept_rate"] = round(
                max(0, toks - disp) / max(disp * spec_len, 1), 4)
        per_tenant["base" if tid == 0 else f"tenant{tid}"] = row
    tenancy = {
        "tenants": tenants,
        "adapter_rank": adapter_rank,
        "adapter_bytes_per_token": pack.bytes_per_token(),
        "per_tenant": per_tenant,
    }
    final_lengths = np.asarray(
        [len(r.prompt) + len(r.tokens) for r in results.values()],
        np.int64)
    kv_bytes = kv_bytes_per_token(engine, final_lengths)
    if spec_len > 0:  # run_spec's per-token walk normalization
        kv_bytes = int(round(kv_bytes * dpt))
    accept = (batcher.accept_rate or 0.0) if spec_len > 0 else None
    return (total_toks / dt, dpt, accept, kv_bytes, weight_bytes, engine,
            tenancy)


# --------------------------------------------------------------------------- #
# --disagg: prefill/decode interference bench (ISSUE 15)
# --------------------------------------------------------------------------- #

# the interference workload: short-prompt decode streams whose inter-token
# gaps we time, plus long shared-prefix prompts arriving mid-stream whose
# chunked prefills are the interference source
_DISAGG_MODEL = dict(
    name="tiny-disagg", num_hidden_layers=4, num_attention_heads=8,
    num_key_value_heads=8, hidden_size=256, intermediate_size=1024,
    vocab_size=4096, max_position_embeddings=256, dtype="float32",
    attention_impl="sdpa")
_DISAGG_SIZES = dict(slots=3, stream_prompt=8, stream_tokens=48,
                     long_prompt=96, long_shared=64, n_streams=2,
                     n_long=3, prefill_chunk=16, page_len=16)


def _launch_replica(cfg_path: str, role: str, slots: int):
    """One serve.py replica as a SUBPROCESS (its own interpreter + GIL —
    the honest CPU proxy for a disaggregated host). Returns
    (Popen, port) once the CLI's "serving" event line reports the
    ephemeral port; a reader thread keeps draining stdout after that."""
    import subprocess
    import threading

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONUNBUFFERED="1")
    proc = subprocess.Popen(
        [sys.executable, "-m", "picotron_tpu.tools.serve",
         "--config", cfg_path, "--random-init", "--port", "0",
         "--slots", str(slots), "--role", role,
         "--stall-timeout", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
        env=env)
    # readline() blocks with no timeout of its own: a replica that wedges
    # before printing the serving event would hang the smoke forever. The
    # timer kill turns that into EOF -> a loud launch failure at 180s.
    watchdog = threading.Timer(180.0, proc.kill)
    watchdog.start()
    port = None
    try:
        while True:
            line = proc.stdout.readline()
            if not line:  # EOF: the child exited (or the watchdog fired)
                raise RuntimeError(
                    f"replica (role={role}) died (or hung past the launch "
                    f"deadline) before reporting a port")
            try:
                evt = json.loads(line)
            except ValueError:
                continue
            if evt.get("evt") == "serving":
                port = evt["port"]
                break
    except BaseException:
        proc.kill()
        raise
    finally:
        watchdog.cancel()
    threading.Thread(target=lambda: [None for _ in proc.stdout],
                     daemon=True).start()
    return proc, port


def _stream_tpot(port: int, prompt, max_new: int, times: list) -> list:
    """Stream one request, appending a perf_counter stamp per token row;
    returns the tokens (the bit-identity cross-check)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    toks = []
    try:
        conn.request("POST", "/generate",
                     json.dumps({"prompt": list(prompt),
                                 "max_new_tokens": max_new,
                                 "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        while True:
            line = resp.readline()
            if not line:
                return toks
            row = json.loads(line)
            if row.get("event") == "token":
                times.append(time.perf_counter())
                toks.append(int(row["token"]))
            elif row.get("event") == "done":
                return toks
    finally:
        conn.close()


def _interference_phase(port: int, sizes: dict, rng, long_prompts) -> tuple:
    """One timed phase against ONE endpoint (a replica or the router):
    ``n_streams`` token-timed decode streams, with ``long_prompts``
    injected once the streams are flowing. Returns (tpot samples,
    stream token lists)."""
    import threading

    stamps = [[] for _ in range(sizes["n_streams"])]
    streams = [[] for _ in range(sizes["n_streams"])]
    threads = []
    for i in range(sizes["n_streams"]):
        prompt = [int(t) for t in
                  rng.integers(1, _DISAGG_MODEL["vocab_size"],
                               sizes["stream_prompt"])]

        def go(i=i, prompt=prompt):
            streams[i].extend(_stream_tpot(
                port, prompt, sizes["stream_tokens"], stamps[i]))

        t = threading.Thread(target=go)
        t.start()
        threads.append(t)
    # inject the long prefills once every stream is past its own prefill
    deadline = time.monotonic() + 60
    while (any(len(s) < 3 for s in stamps)
           and time.monotonic() < deadline):
        time.sleep(0.005)
    longs = []
    for prompt in long_prompts:
        def go_long(prompt=prompt):
            import http.client

            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=600)
            try:
                conn.request("POST", "/generate",
                             json.dumps({"prompt": list(prompt),
                                         "max_new_tokens": 4}),
                             {"Content-Type": "application/json"})
                conn.getresponse().read()
            finally:
                conn.close()

        t = threading.Thread(target=go_long)
        t.start()
        longs.append(t)
    for t in threads + longs:
        t.join(timeout=600)
    samples = []
    for row in stamps:
        samples.extend(b - a for a, b in zip(row[1:], row[2:]))
    return samples, streams


def _p(samples, q):
    import numpy as np

    return float(np.percentile(np.asarray(samples), q)) if samples else None


def run_disagg() -> dict:
    """The mixed-interference A/B/C (CPU proxy; subprocess replicas so
    each role owns an interpreter, the one-host stand-in for separate
    machines):

    - ``baseline``:  decode streams on one colocated (role=both) replica,
      NO long prefills — the no-interference TPOT floor;
    - ``colocated``: same replica shape, long shared-prefix prompts
      arriving mid-stream — their chunked prefills run inside the same
      batcher loop, so every decode slot stalls behind them;
    - ``disagg``:    a prefill + decode two-role fleet behind the
      router — the long prompts' prefills land on the prefill worker and
      stream to the decode worker as KV pages, so the decode batcher
      never spends a dispatch on them.

    Greedy streams are asserted bit-identical across the three phases
    (same seed everywhere); the record carries the TPOT percentiles,
    handoff bytes/latency, and the cluster-wide prefix hit rate."""
    import tempfile

    import numpy as np

    from picotron_tpu.config import RouterConfig
    from picotron_tpu.tools import serve
    from picotron_tpu.tools.router import RouterServer

    sizes = dict(_DISAGG_SIZES)
    rng0 = np.random.default_rng(7)
    shared = [int(t) for t in rng0.integers(
        1, _DISAGG_MODEL["vocab_size"], sizes["long_shared"])]
    long_prompts = []
    for _ in range(sizes["n_long"]):
        tail = [int(t) for t in rng0.integers(
            1, _DISAGG_MODEL["vocab_size"],
            sizes["long_prompt"] - sizes["long_shared"])]
        long_prompts.append(shared + tail)

    raw = {
        "distributed": {"tp_size": 1, "use_cpu": True},
        "model": dict(_DISAGG_MODEL),
        "training": {"seq_length": 64},
        "dataset": {"name": "synthetic"},
        "inference": {"kv_layout": "paged",
                      "kv_page_len": sizes["page_len"],
                      "prefill_chunk": sizes["prefill_chunk"],
                      "decode_block_len": 1},
    }
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(raw, f)
        cfg_path = f.name

    procs = []
    rs = None
    out: dict = {}
    try:
        both_proc, both_port = _launch_replica(cfg_path, "both",
                                               sizes["slots"])
        procs.append(both_proc)

        def warm(port):
            # absorb compiles outside every timed window: the stream
            # shape, the chunked-prefill program, and a page import
            serve._post(port, {"prompt": [1] * sizes["stream_prompt"],
                               "max_new_tokens": 4})
            serve._post(port, {"prompt": list(range(
                1, sizes["long_prompt"] + 1)), "max_new_tokens": 2})

        warm(both_port)
        rng = np.random.default_rng(0)
        base_samples, base_streams = _interference_phase(
            both_port, sizes, rng, [])
        rng = np.random.default_rng(0)
        colo_samples, colo_streams = _interference_phase(
            both_port, sizes, rng, long_prompts)

        pre_proc, pre_port = _launch_replica(cfg_path, "prefill",
                                             sizes["slots"])
        dec_proc, dec_port = _launch_replica(cfg_path, "decode",
                                             sizes["slots"])
        procs += [pre_proc, dec_proc]
        rs = RouterServer(
            [f"127.0.0.1:{pre_port}", f"127.0.0.1:{dec_port}"],
            RouterConfig(probe_interval_s=0.1, scrape_stale_s=5.0),
            log=lambda *a, **k: None)
        rs.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if (len(rs.router._candidates(kind="prefill")) == 1
                    and len(rs.router._eligible()) == 1):
                break
            time.sleep(0.05)
        else:
            raise RuntimeError("disagg fleet never became eligible")
        warm(rs.port)
        rng = np.random.default_rng(0)
        dis_samples, dis_streams = _interference_phase(
            rs.port, sizes, rng, long_prompts)

        # greedy bit-identity across phases: interference must cost
        # latency, never tokens
        assert colo_streams == base_streams == dis_streams, \
            "streams diverged across phases (greedy must be identical)"

        router_stats = rs.router.stats()
        stz = {"prefill": serve._get(pre_port, "/statz")[1],
               "decode": serve._get(dec_port, "/statz")[1]}
        # cluster-wide prefix effectiveness: cached (local radix hits on
        # the prefill worker + remote imports seated on the decode
        # worker) over all prompt tokens the fleet admitted
        cached = sum(s.get("prefix_cached_tokens", 0) for s in stz.values())
        queried = sum(s.get("prefix_queries", 0) for s in stz.values())
        prompt_total = 0
        for s in stz.values():
            # prompt_tokens isn't exported; reconstruct from hit rate
            hr = s.get("prefix_hit_rate")
            ct = s.get("prefix_cached_tokens", 0)
            if hr:
                prompt_total += int(round(ct / hr))
        handoffs = max(1, router_stats["handoffs"].get("served", 0))
        out = {
            "tpot_p50_baseline": _p(base_samples, 50),
            "tpot_p95_baseline": _p(base_samples, 95),
            "tpot_p50_colocated": _p(colo_samples, 50),
            "tpot_p95_colocated": _p(colo_samples, 95),
            "tpot_p50_disagg": _p(dis_samples, 50),
            "tpot_p95_disagg": _p(dis_samples, 95),
            "handoffs_served": router_stats["handoffs"].get("served", 0),
            "handoffs_fallback": router_stats["handoffs"].get(
                "fallback", 0),
            "handoff_bytes_per_request":
                router_stats["handoff_bytes"] // handoffs,
            "handoff_latency_s": router_stats["handoff_s"],
            "cluster_prefix_hit_rate": (
                round(cached / prompt_total, 4) if prompt_total else None),
            "cluster_prefix_queries": queried,
            "decode_worker_handoff_seated":
                stz["decode"].get("handoff_seated", 0),
            "decode_worker_prefill_dispatches":
                stz["decode"].get("prefill_dispatches", 0),
            "sizes": sizes,
        }
        return out
    finally:
        if rs is not None:
            rs.stop()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:  # noqa: BLE001 - teardown best effort
                p.kill()
        os.unlink(cfg_path)


# --------------------------------------------------------------------------- #
# --fleet: elastic fleet controller bench (ISSUE 17)
# --------------------------------------------------------------------------- #

# Sized for a 1-core box: three subprocess jax workers plus the router
# and controller share whatever CPU there is, so the model is as small
# as the serving stack allows and the spike is just deep enough to put
# requests in a queue (3 workers x 1 slot, 5 concurrent streams).
_FLEET_MODEL = dict(
    name="tiny-fleet", num_hidden_layers=2, num_attention_heads=4,
    num_key_value_heads=4, hidden_size=128, intermediate_size=512,
    vocab_size=2048, max_position_embeddings=256, dtype="float32",
    attention_impl="sdpa")
_FLEET_SIZES = dict(slots=1, stream_prompt=8, stream_tokens=16,
                    n_steady=3, n_spike=5, kv_num_pages=128)


def _stream_ttft(port: int, prompt, max_new: int):
    """Stream one request via the router; returns (ttft_s, tokens,
    done_row) — ttft is request-start to first token row on the wire."""
    from picotron_tpu.tools.router import _stream_post

    t0 = time.perf_counter()
    first = {}

    def on_tok(i, row):
        if i == 0:
            first["t"] = time.perf_counter() - t0

    st, rows = _stream_post(port, {"prompt": list(prompt),
                                   "max_new_tokens": max_new},
                            on_token=on_tok)
    toks = [r["token"] for r in rows if r.get("event") == "token"]
    done = [r for r in rows if r.get("event") == "done"]
    if st != 200 or len(done) != 1 or done[0].get("tokens") != toks:
        raise RuntimeError(f"stream failed: HTTP {st}, rows={rows[-2:]}")
    return first.get("t"), toks, done[0]


def run_fleet() -> dict:
    """The elastic-controller rung: a real 3-worker SUBPROCESS fleet
    (serve.py under supervise --serve; a SIGKILL is a real process-group
    death) behind the router, owned by the fleet controller.

    Measures the three latencies that define elasticity on this stack:

    - ``scale_up_latency_s``: controller start to 3 workers launched,
      registered, and router-eligible (cold jax startup included — this
      IS the price of a scale-up on CPU);
    - ``replace_latency_s``: SIGKILL of a worker holding a live routed
      stream to the fleet back at full strength (the stream itself must
      finish exactly-once, greedy bit-identical, via router replay);
    - ``ttft_p95_during_spike`` vs ``ttft_p95_steady``: first-token
      latency under an admission spike that forces a grow decision,
      against the unloaded floor."""
    import tempfile
    import threading

    from picotron_tpu.config import FleetConfig, RouterConfig
    from picotron_tpu.tools.fleet import (FleetController, RouterAdmin,
                                          SubprocessLauncher)
    from picotron_tpu.tools.router import RouterServer, _wait_for

    sizes = dict(_FLEET_SIZES)
    raw = {
        "distributed": {"tp_size": 1, "use_cpu": True},
        "model": dict(_FLEET_MODEL),
        "training": {"seq_length": 64},
        "dataset": {"name": "synthetic"},
        "inference": {"kv_layout": "paged", "kv_page_len": 16,
                      "kv_num_pages": sizes["kv_num_pages"],
                      "decode_block_len": 1},
    }
    with tempfile.NamedTemporaryFile("w", suffix=".json",
                                     delete=False) as f:
        json.dump(raw, f)
        cfg_path = f.name

    # generous stream budgets: a queued spike request legitimately waits
    # for a slot on a contended box, and waiting is what the TTFT delta
    # measures — a mid-queue idle timeout would misread it as a failure
    rcfg = RouterConfig(probe_interval_s=0.2, scrape_stale_s=10.0,
                        connect_timeout_s=30.0,
                        stream_idle_timeout_s=300.0)
    rs = RouterServer([], rcfg, allow_empty=True,
                      log=lambda *a, **k: None)
    rs.start()
    launcher = SubprocessLauncher(
        cfg_path, slots=sizes["slots"],
        serve_args=("--stall-timeout", "0"))
    fcfg = FleetConfig(
        scrape_interval_s=0.5, scrape_timeout_s=5.0, hysteresis=2,
        cooloff_s=2.0, queue_high=0.5, queue_low=0.25, pool_high=0.95,
        pool_low=0.4, min_workers=3, max_workers=4, max_replaces=3,
        replace_backoff_s=0.25, replace_backoff_max_s=2.0,
        drain_timeout_s=60.0)
    ctl = FleetController(fcfg, launcher, RouterAdmin("127.0.0.1", rs.port),
                          log=lambda *a, **k: None)

    def up():
        with ctl._mu:
            return [w for w in ctl.workers.values() if w.state == "up"]

    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    out: dict = {}
    try:
        t0 = time.perf_counter()
        ctl.start()
        if not (_wait_for(lambda: len(up()) >= 3, timeout=600)
                and rs.router.wait_eligible(3, timeout=60)):
            raise RuntimeError("fleet never bootstrapped to 3 workers")
        scale_up_latency_s = time.perf_counter() - t0

        # warm every worker's stream shape, then the steady TTFT floor
        for _ in range(3):
            _stream_ttft(rs.port, prompt, 4)
        steady = []
        oracle = None
        for _ in range(sizes["n_steady"]):
            ttft, toks, _done = _stream_ttft(rs.port, prompt,
                                             sizes["stream_tokens"])
            steady.append(ttft)
            if oracle is None:
                oracle = toks
            elif toks != oracle:
                raise RuntimeError("greedy streams diverged across "
                                   "workers (identical seeds required)")

        # SIGKILL a worker holding this live stream; the router must
        # replay it exactly-once and the controller must replace
        killed = {}

        def kill_at(i, row):
            if i == 4 and not killed:
                busy = None
                for nm, rep in rs.router.replicas.items():
                    with rep._mu:
                        if rep.inflight > 0:
                            busy = nm
                            break
                ws = up()
                for w in ws:
                    if w.router_name == busy:
                        killed["worker"] = w.name
                        w.handle.kill()
                        return
                killed["worker"] = ws[0].name
                ws[0].handle.kill()

        from picotron_tpu.tools.router import _stream_post

        t_kill = time.perf_counter()
        st, rows = _stream_post(rs.port,
                                {"prompt": list(prompt),
                                 "max_new_tokens": sizes["stream_tokens"]},
                                on_token=kill_at)
        toks = [r["token"] for r in rows if r.get("event") == "token"]
        done = [r for r in rows if r.get("event") == "done"]
        if not (st == 200 and killed and len(done) == 1
                and done[0]["replays"] >= 1 and toks == oracle):
            raise RuntimeError(
                f"kill drill stream not exactly-once bit-identical: "
                f"HTTP {st}, killed={killed}, tail={rows[-2:]}")
        if not _wait_for(
                lambda: (ctl.decisions().get("replace", 0) >= 1
                         and len(up()) >= 3), timeout=600):
            raise RuntimeError("dead worker never replaced")
        replace_latency_s = time.perf_counter() - t_kill

        # admission spike: concurrent streams over the fleet; the
        # controller must decide to grow, and nothing may be shed
        grow0 = ctl.decisions().get("grow", 0)
        spike_ttfts: list = []
        spike_errs: list = []

        def spike_one():
            try:
                ttft, toks, _d = _stream_ttft(rs.port, prompt,
                                              sizes["stream_tokens"])
                if toks != oracle:
                    raise RuntimeError("spike stream diverged")
                if ttft is not None:
                    spike_ttfts.append(ttft)
            except Exception as e:  # noqa: BLE001 - collected and gated
                spike_errs.append(repr(e))

        threads = [threading.Thread(target=spike_one)
                   for _ in range(sizes["n_spike"])]
        for t in threads:
            t.start()
        grew = _wait_for(
            lambda: ctl.decisions().get("grow", 0) > grow0, timeout=60)
        for t in threads:
            t.join(timeout=600)
        if spike_errs:
            raise RuntimeError(f"spike streams failed: {spike_errs[:3]}")
        shed = rs.router.stats()["requests"]["shed"]
        out = {
            "scale_up_latency_s": round(scale_up_latency_s, 3),
            "replace_latency_s": round(replace_latency_s, 3),
            "ttft_p95_steady": _p(steady, 95),
            "ttft_p50_steady": _p(steady, 50),
            "ttft_p95_during_spike": _p(spike_ttfts, 95),
            "ttft_p50_during_spike": _p(spike_ttfts, 50),
            "grow_decided": bool(grew),
            "spike_shed": int(shed),
            "decisions": ctl.decisions(),
            "sizes": sizes,
        }
        return out
    finally:
        ctl.stop(drain_workers=True)
        rs.stop()
        os.unlink(cfg_path)


def run_dp(dp: int) -> dict:
    """dp-sharded continuous batching (CPU proxy): the SAME tiny-model
    batcher workload at dp=1 and dp=N — one logical engine whose slot axis
    spans the dp mesh axis, paged KV pool sharded with it, rebalance
    planner armed. The workload is shaped to skew occupancy (long streams
    land on shard 0, short ones on shard 1 finish early), so the planner
    must migrate a slot's pages across shards mid-run through the
    page-transport device path while streams keep decoding.

    Gates (enforced by main's --dp branch / ``make dp-smoke``):
    - greedy token streams at dp=N are BIT-IDENTICAL to dp=1;
    - ``slots_total == dp * slots_per_shard`` (the global slot map);
    - zero dp-axis collectives traced during the whole run — prompts fit
      one prefill chunk, so even the chunked-prefill owner-reduce (the one
      dp collective the engine owns) never appears, and the decode hot
      path is verified shard-local via the comm_trace channel;
    - the rebalance planner fired at least once (the workload is
      deterministic, so this pins that migration happens OFF the jitted
      dispatch path yet streams stay exact).
    """
    import contextlib
    import io

    import jax

    from picotron_tpu.config import Config
    from picotron_tpu.inference import (
        ContinuousBatcher,
        InferenceEngine,
        Request,
    )
    from picotron_tpu.models import llama

    model = dict(
        name="tiny", num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, hidden_size=64, intermediate_size=128,
        vocab_size=256, max_position_embeddings=96, dtype="float32",
        attention_impl="sdpa")

    def one(d: int) -> dict:
        cfg = Config.from_dict({
            "distributed": {"tp_size": 1, "use_cpu": True},
            "model": dict(model),
            "training": {"seq_length": 96},
            "dataset": {"name": "synthetic"},
            "inference": {"dp_size": d, "kv_layout": "paged",
                          "kv_page_len": 8},
        })
        engine = InferenceEngine(cfg, slots=4, max_seq_len=96,
                                 decode_block_len=4)
        params = engine.shard_params(jax.jit(
            lambda k: llama.init_params(k, cfg.model))(
                jax.random.PRNGKey(0)))
        b = ContinuousBatcher(engine, params)
        skew = [0]

        def on_tokens(uid, toks):
            occ = b.shard_occupancy()
            skew[0] = max(skew[0], max(occ) - min(occ))

        b.on_tokens = on_tokens
        reqs = [Request("l0", [1, 2, 3, 4, 5], max_new_tokens=28),
                Request("l1", [9, 8, 7, 6], max_new_tokens=28),
                Request("s0", [11, 12], max_new_tokens=4),
                Request("s1", [13, 14, 15], max_new_tokens=4)]
        # comm_trace capture: PICOTRON_VERBOSE=1 prints one stderr line
        # per collective per trace — a dp-axis line during this window
        # would mean the sharded hot path grew cross-shard traffic
        old = os.environ.get("PICOTRON_VERBOSE")
        os.environ["PICOTRON_VERBOSE"] = "1"
        buf = io.StringIO()
        t0 = time.perf_counter()
        try:
            with contextlib.redirect_stderr(buf):
                res = b.run(reqs)
        finally:
            if old is None:
                os.environ.pop("PICOTRON_VERBOSE", None)
            else:
                os.environ["PICOTRON_VERBOSE"] = old
        dt = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in res.values())
        dp_comms = [ln for ln in buf.getvalue().splitlines()
                    if ln.startswith("[comm]") and "axis=dp" in ln]
        st = b.stats()
        return {
            "streams": {uid: r.tokens for uid, r in res.items()},
            "tokens_per_s": toks / dt if dt > 0 else 0.0,
            "stats": st,
            "dispatch_latency_s": dispatch_latency_summary(engine),
            "dp_comm_lines": dp_comms,
            "occupancy_skew_peak": skew[0],
            "slots_per_shard": engine.slots_per_shard,
        }

    base, sharded = one(1), one(dp)
    st = sharded["stats"]
    return {
        "dp_size": st["dp_size"],
        "slots_total": st["slots_total"],
        "slots_per_shard": sharded["slots_per_shard"],
        "shard_occupancy": st["shard_occupancy"],
        "occupancy_skew_peak": sharded["occupancy_skew_peak"],
        "rebalance_count": st["rebalance_count"],
        "rebalance_bytes": st["rebalance_bytes"],
        "tokens_per_s_dp1": round(base["tokens_per_s"], 1),
        "tokens_per_s_dpN": round(sharded["tokens_per_s"], 1),
        "dispatch_latency_s": {"dp1": base["dispatch_latency_s"],
                               f"dp{dp}": sharded["dispatch_latency_s"]},
        "dp_collectives_traced": len(sharded["dp_comm_lines"]),
        "dp_comm_lines": sharded["dp_comm_lines"][:8],
        "streams_match": base["streams"] == sharded["streams"],
    }


def run_overlap(synthetic_s: float) -> dict:
    """Zero-bubble overlapped scheduling A/B (CPU proxy): the SAME
    tiny-model batcher workload with ``inference.overlap`` off and on,
    same seed, same per-slot key schedule. The tiny CPU model produces
    no hideable device time of its own, so the batcher's synthetic-sync
    knob pads every round's device window to ``synthetic_s`` and an
    ``on_tokens`` sleeper injects per-token host delivery work sized so
    per-round host work matches it — the "host work and device time
    comparable" regime the pipeline exists for. Off mode pays
    device + host serially per round; on mode hides the host walk of
    round N inside round N+1's device window.

    Gates (enforced by main's --overlap branch / ``make overlap-smoke``):
    - token streams BIT-IDENTICAL on vs off (the tentpole invariant);
    - overlap-on ``dispatch_gap_s`` p50 <= 0.5x overlap-off (the
      pipeline is gapless by construction while a round is in flight);
    - overlap-on tokens/s >= 1.3x overlap-off.
    """
    import jax

    from picotron_tpu.config import Config
    from picotron_tpu.inference import (
        ContinuousBatcher,
        InferenceEngine,
        Request,
    )
    from picotron_tpu.models import llama

    model = dict(
        name="tiny", num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, hidden_size=64, intermediate_size=128,
        vocab_size=256, max_position_embeddings=160, dtype="float32",
        attention_impl="sdpa")
    slots, block, new_toks = 4, 4, 40
    # per-token host delivery work sized so a full round's walk (slots *
    # block tokens) plus the batcher's own per-round scheduling overhead
    # lands NEAR the synthetic device window without exceeding it — on
    # the hidden side of the pipeline, host work past the device window
    # becomes the bottleneck again and the A/B only measures noise
    host_tok_s = synthetic_s / (2 * slots * block)

    def one(overlap: bool) -> dict:
        cfg = Config.from_dict({
            "distributed": {"tp_size": 1, "use_cpu": True},
            "model": dict(model),
            "training": {"seq_length": 160},
            "dataset": {"name": "synthetic"},
            "inference": {"overlap": overlap, "key_schedule": "slot"},
        })
        engine = InferenceEngine(cfg, slots=slots, max_seq_len=160,
                                 decode_block_len=block)
        params = engine.shard_params(jax.jit(
            lambda k: llama.init_params(k, cfg.model))(
                jax.random.PRNGKey(0)))
        b = ContinuousBatcher(engine, params, seed=7)
        # warm the jitted prefill/decode programs OUTSIDE the timed
        # window — a FULL batch at the measured prompt length, so the
        # measured run recompiles nothing — then arm the delay knobs
        b.run([Request(f"warm{i}", [3, 1, 4, 1, 5],
                       max_new_tokens=block) for i in range(slots)])
        b._synthetic_sync_s = synthetic_s
        b.on_tokens = lambda uid, toks: time.sleep(host_tok_s * len(toks))
        reqs = [Request(f"r{i}", [(7 * i + j) % 199 + 1 for j in range(5)],
                        max_new_tokens=new_toks) for i in range(slots)]
        t0 = time.perf_counter()
        res = b.run(reqs)
        dt = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in res.values())
        st = b.stats()
        return {
            "streams": {uid: r.tokens for uid, r in res.items()},
            "tokens_per_s": toks / dt if dt > 0 else 0.0,
            "overlap": st["overlap"],
            "last_host_sync_s": st.get("last_host_sync_s"),
        }

    off, on = one(False), one(True)

    def p50(leg):
        gap = leg["overlap"].get("dispatch_gap_s") or {}
        return gap.get("p50")

    return {
        "synthetic_device_s": synthetic_s,
        "host_token_s": host_tok_s,
        "tokens_per_s_off": round(off["tokens_per_s"], 1),
        "tokens_per_s_on": round(on["tokens_per_s"], 1),
        "speedup": round(on["tokens_per_s"]
                         / max(off["tokens_per_s"], 1e-9), 3),
        "dispatch_gap_s": {"off": off["overlap"].get("dispatch_gap_s"),
                           "on": on["overlap"].get("dispatch_gap_s")},
        "dispatch_gap_p50_off": p50(off),
        "dispatch_gap_p50_on": p50(on),
        "host_work_s": {"off": off["overlap"].get("host_work_s"),
                        "on": on["overlap"].get("host_work_s")},
        "overlap_efficiency": on["overlap"].get("overlap_efficiency"),
        "device_busy_s": on["overlap"].get("device_busy_s"),
        "wall_s": on["overlap"].get("wall_s"),
        "streams_match": off["streams"] == on["streams"],
    }


def run_mixed() -> dict:
    """Mixed prefill–decode dispatch A/B (CPU proxy): long prompts keep
    arriving while a batch of decoders is mid-stream, with
    ``inference.mixed_dispatch`` off (serial admission prefill: every
    chunk is a solo dispatch the seated decoders wait out) and on (the
    chunk rides the fused lane of the decode dispatch itself). Three
    legs, same seed, per-slot key schedule pinned on both sides:

    - ``floor``: decoders only, mixed on — the no-prefill TPOT floor;
    - ``on``:    decoders + arriving long prompts, mixed on;
    - ``off``:   the identical workload, mixed off (serial + gate).

    TPOT is the pooled inter-token gap of the DECODER streams (their
    own first token excluded); TTFT is submit-to-first-token of the
    long prompts. Gates (enforced by main's --mixed branch /
    ``make mixed-smoke``):

    - token streams BIT-IDENTICAL on vs off (the tentpole invariant);
    - decode TPOT p95 under concurrent prefill (on) <= 3x the
      no-prefill floor — prompts land without stalling decode;
    - TTFT p95 on <= 3x off — admission through the lane stays at its
      feed rate, ceil(prompt/chunk) rounds to first token. The bound
      is a CPU-proxy allowance, not a target: here a solo B=1 chunk
      dispatch costs ~1/3 of a full fused round (per-dispatch python
      overhead dominates), so the serial leg's TTFT is structurally
      understated relative to an accelerator, where a C-token chunk
      and a slots*block decode round do comparable work;
    - the on leg actually moved prompt tokens through the lane
      (``picotron_prefill_lane_tokens_total`` > 0).
    """
    import jax

    from picotron_tpu.config import Config
    from picotron_tpu.inference import (
        ContinuousBatcher,
        InferenceEngine,
        Request,
    )
    from picotron_tpu.models import llama

    model = dict(
        name="tiny", num_hidden_layers=2, num_attention_heads=4,
        num_key_value_heads=4, hidden_size=64, intermediate_size=128,
        vocab_size=256, max_position_embeddings=160, dtype="float32",
        attention_impl="sdpa")
    slots, block, chunk = 4, 4, 8
    decoders = 3          # long-running decode streams (the TPOT probes)
    long_prompt = 24      # 3 lane chunks at chunk=8; > chunk so it lanes
    # arrivals land mid-decode, triggered by d0's token count — spaced
    # wider than the 3 rounds a 24-token prompt occupies the lane, so
    # TTFT measures the prefill path itself, not queue backlog behind a
    # saturated lane (arrival-rate <= lane-feed-rate is the regime the
    # fused lane serves; past saturation every scheme queues)
    arrive_at_tok = {8: 0, 24: 1, 40: 2}

    def one(mixed: bool, with_prefill: bool) -> dict:
        cfg = Config.from_dict({
            "distributed": {"tp_size": 1, "use_cpu": True},
            "model": dict(model),
            "training": {"seq_length": 160},
            "dataset": {"name": "synthetic"},
            "inference": {"mixed_dispatch": mixed, "prefill_chunk": chunk,
                          "key_schedule": "slot"},
        })
        engine = InferenceEngine(cfg, slots=slots, max_seq_len=160,
                                 decode_block_len=block)
        params = engine.shard_params(jax.jit(
            lambda k: llama.init_params(k, cfg.model))(
                jax.random.PRNGKey(0)))
        b = ContinuousBatcher(engine, params, seed=7)
        # warm every program the measured run needs OUTSIDE the timed
        # window: the (fused, when mixed) decode family at full batch,
        # the short-prompt prefill bucket, and the long-prompt path
        # (lane chunks when mixed, bucketed serial prefill when not)
        b.run([Request(f"warm{i}", [3, 1, 4, 1, 5], max_new_tokens=block)
               for i in range(slots - 1)]
              + [Request("warmL", [2 * j % 199 + 1
                                   for j in range(long_prompt)],
                         max_new_tokens=block)])
        # two measured repeats on the SAME warmed batcher (no
        # recompiles): the gates use the per-leg MIN p95, which
        # de-noises scheduler hiccups on both sides of every ratio —
        # with 3 TTFT samples per repeat a p95 is effectively a max,
        # and one preempted leg would otherwise fail a sound gate
        streams: dict = {}
        tpots, ttfts = [], []
        for rep in range(2):
            t_tok: dict = {}
            sub_t: dict = {}
            fired: set = set()
            d0 = f"d{rep}.0"

            def on_tokens(uid, toks, t_tok=t_tok, sub_t=sub_t,
                          fired=fired, d0=d0, rep=rep):
                ts = t_tok.setdefault(uid, [])
                for _ in toks:
                    ts.append(time.perf_counter())
                    k = arrive_at_tok.get(len(ts)) if uid == d0 else None
                    if with_prefill and k is not None and k not in fired:
                        fired.add(k)
                        r = Request(f"L{rep}.{k}",
                                    [(5 * k + 3 * j) % 199 + 1
                                     for j in range(long_prompt)],
                                    max_new_tokens=4)
                        sub_t[r.uid] = time.perf_counter()
                        b.submit(r)

            b.on_tokens = on_tokens
            # the decoders: short (sub-chunk) prompts, long streams,
            # and a TPOT SLO so the off leg's admissions run through
            # the ARMED prefill gate — serial+gate, not bare serial
            res = b.run([Request(f"d{rep}.{i}",
                                 [(7 * i + j) % 199 + 1
                                  for j in range(5)],
                                 max_new_tokens=60, tpot_slo_ms=50.0)
                         for i in range(decoders)])
            tpots.append(_p(
                [dt for uid, ts in t_tok.items() if uid.startswith("d")
                 for dt in (t1 - t0 for t0, t1 in zip(ts, ts[1:]))], 95))
            ttft = [t_tok[uid][0] - t for uid, t in sub_t.items()
                    if uid in t_tok]
            ttfts.append(_p(ttft, 95) if ttft else None)
            # the key chain advances one split per admission — the same
            # count in both modes — so repeat r's streams match across
            # legs (and only across the same r); uids carry the repeat
            streams.update({uid: r.tokens for uid, r in res.items()
                            if uid.startswith(("d", "L"))})
        snap = b.obs.registry.snapshot()

        def total(name, field=None):
            fam = snap.get(name)
            if not fam:
                return 0
            vals = fam["values"].values()
            return sum(v[field] for v in vals) if field else sum(vals)

        toks = sum(len(t) for t in streams.values())
        return {
            "streams": streams,
            "tpot_p95_s": min(tpots),
            "ttft_p95_s": (min(t for t in ttfts if t is not None)
                           if any(t is not None for t in ttfts)
                           else None),
            "lane_tokens": total("picotron_prefill_lane_tokens_total"),
            "decode_stalls": total("picotron_decode_stall_seconds",
                                   "count"),
            "dispatches_per_token": round(
                (b.decode_dispatches + b.prefill_dispatches)
                / max(toks, 1), 3),
        }

    floor = one(True, False)
    on = one(True, True)
    off = one(False, True)
    return {
        "tpot_floor_p95_s": floor["tpot_p95_s"],
        "tpot_on_p95_s": on["tpot_p95_s"],
        "tpot_off_p95_s": off["tpot_p95_s"],
        "tpot_vs_floor": round(on["tpot_p95_s"]
                               / max(floor["tpot_p95_s"], 1e-9), 3),
        "ttft_on_p95_s": on["ttft_p95_s"],
        "ttft_off_p95_s": off["ttft_p95_s"],
        "lane_tokens_on": on["lane_tokens"],
        "lane_tokens_off": off["lane_tokens"],
        "decode_stalls_on": on["decode_stalls"],
        "decode_stalls_off": off["decode_stalls"],
        "dispatches_per_token": {"on": on["dispatches_per_token"],
                                 "off": off["dispatches_per_token"]},
        "streams_match": on["streams"] == off["streams"],
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="decode throughput bench")
    ap.add_argument("--block-len", type=int, default=1,
                    help="decode steps fused per dispatch (1 = per-token "
                         "loop; N = blocked fast path, 1/N dispatches per "
                         "token)")
    ap.add_argument("--spec-len", type=int, default=0,
                    help="speculative decoding: draft tokens per verify "
                         "dispatch on repetitive prompts (0 = off; "
                         "mutually exclusive with --block-len > 1)")
    ap.add_argument("--drafter", choices=("ngram", "learned"),
                    default="ngram",
                    help="draft model for --spec-len runs: the model-free "
                         "prompt-lookup drafter (default) or the "
                         "EAGLE-style learned head over the target's own "
                         "last hidden state (shares the target's "
                         "embedding + lm_head; one small jitted draft "
                         "dispatch per round)")
    ap.add_argument("--disagg", action="store_true",
                    help="prefill/decode interference bench (CPU proxy): "
                         "decode-stream TPOT with long shared-prefix "
                         "prefills arriving mid-stream, measured "
                         "baseline (no interference) vs colocated vs a "
                         "disaggregated prefill+decode fleet behind the "
                         "router — the JSON gains tpot_p95_colocated / "
                         "tpot_p95_disagg, handoff_bytes_per_request, "
                         "handoff_latency_s, cluster_prefix_hit_rate")
    ap.add_argument("--fleet", action="store_true",
                    help="elastic fleet controller bench (CPU proxy): a "
                         "3-worker subprocess fleet behind the router "
                         "under tools/fleet.py — SIGKILL-under-load "
                         "replacement and an admission spike that forces "
                         "a grow decision; the JSON gains "
                         "scale_up_latency_s, replace_latency_s, and "
                         "ttft_p95_during_spike vs ttft_p95_steady")
    ap.add_argument("--spec-auto", action="store_true",
                    help="closed-loop controller run: a mixed "
                         "repetitive/random-prompt workload through the "
                         "real batcher with inference.spec_controller "
                         "enabled — the JSON gains spec_len_effective, "
                         "accept_rate_by_drafter, per-regime "
                         "dispatches/token, and controller-decision "
                         "counts (requires --spec-len)")
    ap.add_argument("--attend-impl", choices=("dense", "flash"),
                    default="dense",
                    help="KV-cache attention kernel: the dense "
                         "whole-window einsum (default) or the "
                         "length-aware Pallas flash decode (interpret "
                         "mode off TPU — a parity surface, not a CPU "
                         "perf one)")
    ap.add_argument("--kv-layout", choices=("contiguous", "paged"),
                    default="contiguous",
                    help="KV cache layout: per-slot contiguous strips "
                         "(default) or the paged pool with block-table "
                         "indirection (inference/paged_kv.py) — the JSON "
                         "then adds kv_pages_total/live, pool "
                         "utilization, and prefix_hit_rate")
    ap.add_argument("--kv-page-policy", choices=("uniform", "hot_bf16"),
                    default="uniform",
                    help="per-page storage policy (paged layout only): "
                         "hot_bf16 reads radix-shared prefix pages at "
                         "full precision and exclusively-held tails as "
                         "int8 + scales — kv_bytes_per_token then "
                         "reflects the live-page mix")
    ap.add_argument("--sample-on-device", action="store_true",
                    help="fused sampling epilogue: prefill/decode "
                         "dispatches sample inside the jitted program "
                         "and ship token ids, never [B, vocab] logits — "
                         "logits_bytes_to_host_per_token drops from "
                         "vocab*4 to O(B)")
    ap.add_argument("--weight-dtype", choices=("bf16", "int8"),
                    default="bf16",
                    help="weight storage: bf16 (the model dtype, "
                         "default) or per-channel int8 served through "
                         "the fused dequant matmul — weight_bytes_total "
                         "in the JSON drops to ~half the bf16 bytes")
    ap.add_argument("--tenants", type=int, default=0,
                    help="multi-tenant run: N rank-R LoRA adapters over "
                         "one shared base, mixed with base-only rows in "
                         "the SAME continuous batch (every dispatch "
                         "serves several tenants through the segmented "
                         "adapter matmul) — the JSON gains per-tenant "
                         "tokens/dpt/TTFT (+ accept with --spec-len) and "
                         "adapter_bytes_per_token (composes with "
                         "--weight-dtype int8 and --spec-len)")
    ap.add_argument("--adapter-rank", type=int, default=8,
                    help="LoRA rank for --tenants adapters (default 8)")
    ap.add_argument("--dp", type=int, default=1,
                    help="dp-sharded batching smoke (CPU proxy): run the "
                         "continuous batcher as ONE logical engine whose "
                         "slot axis spans N dp shards, vs the dp=1 "
                         "baseline — the JSON gains dp_size, slots_total, "
                         "per-shard occupancy skew, rebalance_count/"
                         "bytes, and dispatch-latency percentiles at "
                         "both widths; gates bit-identical streams and a "
                         "collective-free decode hot path")
    ap.add_argument("--overlap", choices=("ab",), default=None,
                    help="zero-bubble overlapped-scheduling A/B (CPU "
                         "proxy): the SAME batcher workload with "
                         "inference.overlap off then on, synthetic "
                         "device windows + injected per-token host work "
                         "— the JSON gains dispatch_gap_s percentiles, "
                         "host_work_s, overlap_efficiency, and the "
                         "off/on tokens/s; gates bit-identical streams, "
                         "gap p50 <= 0.5x off, tokens/s >= 1.3x off")
    ap.add_argument("--synthetic-device-s", type=float, default=0.02,
                    help="--overlap ab: pad every round's device window "
                         "to this many seconds via the batcher's "
                         "synthetic-sync knob (models hideable device "
                         "time the tiny CPU model lacks; default 20ms)")
    ap.add_argument("--mixed", choices=("ab",), default=None,
                    help="mixed prefill-decode dispatch A/B (CPU proxy): "
                         "long prompts arriving mid-decode with "
                         "inference.mixed_dispatch off then on, plus a "
                         "decoders-only TPOT floor leg — the JSON gains "
                         "decode TPOT p95 / TTFT p95 / lane-token / "
                         "stall-count comparisons; gates bit-identical "
                         "streams, TPOT p95 under concurrent prefill "
                         "<= 3x the floor, TTFT p95 <= 3x serial")
    args = ap.parse_args(argv)
    if args.mixed:
        # the mixed smoke is its own protocol (three batcher legs,
        # fused lane off vs on vs no-prefill floor; stream-exactness +
        # stall-closure gates, not absolute tokens/s) — CPU proxy
        if args.disagg or args.fleet or args.tenants or args.spec_len \
                or args.dp > 1 or args.overlap:
            ap.error("--mixed is its own protocol; drop the other "
                     "mode flags")
        platform = require_accelerator("bench_decode.py")
        try:
            res = run_mixed()
        except Exception as e:  # noqa: BLE001 - the record IS the channel
            print(json.dumps({
                "metric": "mixed_dispatch_cpu_smoke", "value": None,
                "unit": "s", "vs_baseline": None,
                "code_failure": True,
                "error": f"{type(e).__name__}: {e}"[:800]}))
            raise
        print(f"# mixed bench: tpot_p95 floor={res['tpot_floor_p95_s']} "
              f"on={res['tpot_on_p95_s']} off={res['tpot_off_p95_s']} "
              f"(on/floor {res['tpot_vs_floor']}x) "
              f"ttft_p95 on={res['ttft_on_p95_s']} "
              f"off={res['ttft_off_p95_s']} "
              f"lane_tokens={res['lane_tokens_on']} "
              f"stalls off={res['decode_stalls_off']} "
              f"on={res['decode_stalls_on']} "
              f"streams_match={res['streams_match']}",
              file=sys.stderr)
        record = {"metric": "mixed_dispatch_cpu_smoke",
                  "value": res["tpot_on_p95_s"], "unit": "s",
                  "vs_baseline": None, "platform": platform, **res}
        print(json.dumps(record))
        # the gates: the fused lane must change NOTHING about the
        # emitted streams, keep decode within 3x its no-prefill floor
        # while prompts land, actually carry the prompts (lane tokens),
        # and not starve admission relative to the serial path
        if not res["streams_match"]:
            raise SystemExit("mixed gate failed: mixed-on streams "
                             "diverge from mixed-off")
        if not res["lane_tokens_on"]:
            raise SystemExit("mixed gate failed: no prompt tokens moved "
                             "through the lane in the on leg")
        if res["lane_tokens_off"]:
            raise SystemExit("mixed gate failed: the mixed-off leg "
                             "moved tokens through the lane")
        if res["tpot_vs_floor"] > 3.0:
            raise SystemExit(
                f"mixed gate failed: decode TPOT p95 under concurrent "
                f"prefill {res['tpot_on_p95_s']:.6f}s > 3x no-prefill "
                f"floor {res['tpot_floor_p95_s']:.6f}s")
        if res["ttft_on_p95_s"] is None or res["ttft_off_p95_s"] is None:
            raise SystemExit("mixed gate failed: missing TTFT "
                             "percentiles")
        if res["ttft_on_p95_s"] > 3.0 * res["ttft_off_p95_s"]:
            raise SystemExit(
                f"mixed gate failed: TTFT p95 on {res['ttft_on_p95_s']:.6f}s "
                f"> 3x serial {res['ttft_off_p95_s']:.6f}s")
        return
    if args.overlap:
        # the overlap smoke is its own protocol (one batcher workload,
        # pipeline off vs on; stream-exactness + bubble-closure gates,
        # not absolute tokens/s) — CPU proxy by design
        if args.disagg or args.fleet or args.tenants or args.spec_len \
                or args.dp > 1:
            ap.error("--overlap is its own protocol; drop the other "
                     "mode flags")
        platform = require_accelerator("bench_decode.py")
        try:
            res = run_overlap(args.synthetic_device_s)
        except Exception as e:  # noqa: BLE001 - the record IS the channel
            print(json.dumps({
                "metric": "overlap_scheduling_cpu_smoke", "value": None,
                "unit": "tokens/s", "vs_baseline": None,
                "code_failure": True,
                "error": f"{type(e).__name__}: {e}"[:800]}))
            raise
        print(f"# overlap bench: tokens/s off={res['tokens_per_s_off']} "
              f"on={res['tokens_per_s_on']} "
              f"(speedup {res['speedup']}x) "
              f"gap_p50 off={res['dispatch_gap_p50_off']} "
              f"on={res['dispatch_gap_p50_on']} "
              f"overlap_efficiency={res['overlap_efficiency']} "
              f"streams_match={res['streams_match']}",
              file=sys.stderr)
        record = {"metric": "overlap_scheduling_cpu_smoke",
                  "value": res["tokens_per_s_on"], "unit": "tokens/s",
                  "vs_baseline": None, "platform": platform, **res}
        print(json.dumps(record))
        # the gates: the pipeline must change NOTHING about the emitted
        # streams, close the issue-to-issue bubble, and convert the
        # closed bubble into throughput in the comparable-host regime
        if not res["streams_match"]:
            raise SystemExit("overlap gate failed: overlap-on streams "
                             "diverge from overlap-off")
        g_off, g_on = (res["dispatch_gap_p50_off"],
                       res["dispatch_gap_p50_on"])
        if g_off is None or g_on is None:
            raise SystemExit("overlap gate failed: missing dispatch-gap "
                             "percentiles")
        if g_on > 0.5 * g_off:
            raise SystemExit(
                f"overlap gate failed: on gap p50 {g_on:.6f}s > 0.5x "
                f"off {g_off:.6f}s")
        if res["speedup"] < 1.3:
            raise SystemExit(
                f"overlap gate failed: speedup {res['speedup']}x < 1.3x "
                f"with host work ~= device time")
        return
    if args.dp > 1:
        # the dp smoke is its own protocol (an A/B of one batcher workload
        # at two mesh widths; stream-exactness gates, not tokens/s) — CPU
        # proxy by design, over the forced multi-device host platform the
        # module-top bootstrap set up before jax loaded
        if args.disagg or args.fleet or args.tenants or args.spec_len:
            ap.error("--dp is its own protocol; drop the other mode flags")
        platform = require_accelerator("bench_decode.py")
        try:
            res = run_dp(args.dp)
        except Exception as e:  # noqa: BLE001 - the record IS the channel
            print(json.dumps({
                "metric": "dp_sharded_batching_cpu_smoke", "value": None,
                "unit": "tokens/s", "vs_baseline": None,
                "code_failure": True,
                "error": f"{type(e).__name__}: {e}"[:800]}))
            raise
        print(f"# dp bench: dp={res['dp_size']} "
              f"slots_total={res['slots_total']} "
              f"occupancy_skew_peak={res['occupancy_skew_peak']} "
              f"rebalances={res['rebalance_count']} "
              f"({res['rebalance_bytes']}B) "
              f"tokens/s dp1={res['tokens_per_s_dp1']} "
              f"dp{args.dp}={res['tokens_per_s_dpN']} "
              f"streams_match={res['streams_match']} "
              f"dp_collectives={res['dp_collectives_traced']}",
              file=sys.stderr)
        record = {"metric": "dp_sharded_batching_cpu_smoke",
                  "value": res["tokens_per_s_dpN"], "unit": "tokens/s",
                  "vs_baseline": None, "platform": platform, **res}
        print(json.dumps(record))
        # the gates: the sharded engine must be indistinguishable from
        # the dp=1 one token-for-token, expose the global slot map, keep
        # the hot path free of cross-shard collectives, and have actually
        # exercised the migration planner (the workload forces the skew)
        if not res["streams_match"]:
            raise SystemExit("dp gate failed: dp-sharded streams diverge "
                             "from the dp=1 baseline")
        if res["slots_total"] != args.dp * res["slots_per_shard"]:
            raise SystemExit(
                f"dp gate failed: slots_total {res['slots_total']} != "
                f"dp {args.dp} x slots_per_shard {res['slots_per_shard']}")
        if res["dp_collectives_traced"]:
            raise SystemExit(
                "dp gate failed: dp-axis collectives on the serving path: "
                + "; ".join(res["dp_comm_lines"]))
        if not res["rebalance_count"]:
            raise SystemExit("dp gate failed: the skewed workload never "
                             "triggered a cross-shard slot migration")
        return
    if args.disagg:
        # the disagg bench is its own protocol (subprocess fleet + the
        # router; TPOT percentiles, not tokens/s). Its serve.py replicas
        # are children pinned to the CPU (_launch_replica) and never
        # touch the chip: the record names both platforms
        platform = require_accelerator("bench_decode.py")
        try:
            res = run_disagg()
        except Exception as e:  # noqa: BLE001 - the record IS the channel
            print(json.dumps({
                "metric": "disagg_interference_cpu_smoke", "value": None,
                "unit": "tpot_p95_s", "vs_baseline": None,
                "code_failure": True,
                "error": f"{type(e).__name__}: {e}"[:800]}))
            raise
        base, colo, dis = (res["tpot_p95_baseline"],
                           res["tpot_p95_colocated"],
                           res["tpot_p95_disagg"])
        if None in (base, colo, dis):
            # a phase delivered too few tokens to sample TPOT at all:
            # that is a failed measurement, and the record must say so
            # in the structured channel, not via a raw format TypeError
            print(json.dumps({
                "metric": "disagg_interference_cpu_smoke", "value": None,
                "unit": "tpot_p95_s", "vs_baseline": None,
                "code_failure": True,
                "error": "a phase produced no TPOT samples "
                         f"(p95s: baseline={base} colocated={colo} "
                         f"disagg={dis})", **res}))
            raise SystemExit("disagg bench: empty TPOT sample set")
        print(f"# disagg bench: tpot_p95 baseline={base:.4f}s "
              f"colocated={colo:.4f}s disagg={dis:.4f}s "
              f"handoffs={res['handoffs_served']} "
              f"handoff_bytes/req={res['handoff_bytes_per_request']} "
              f"cluster_prefix_hit_rate={res['cluster_prefix_hit_rate']}",
              file=sys.stderr)
        record = {"metric": "disagg_interference_cpu_smoke",
                  "value": round(dis, 5), "unit": "tpot_p95_s",
                  "vs_baseline": None, "platform": platform,
                  "replica_platform": "cpu", **res}
        print(json.dumps(record))
        # the smoke gate (make disagg-smoke): interference must
        # measurably degrade the COLOCATED configuration while the
        # disaggregated decode worker stays near its no-prefill floor.
        # The ordering is the hard gate; the 10%-of-baseline acceptance
        # is recorded (p95s on a shared CPU box carry scheduler noise).
        if not (colo > dis):
            raise SystemExit(
                f"disagg gate failed: colocated p95 {colo:.4f}s is not "
                f"worse than disaggregated {dis:.4f}s")
        return
    if args.fleet:
        # the fleet bench is its own protocol (subprocess fleet + the
        # elastic controller; elasticity latencies, not tokens/s). Its
        # workers are children pinned to the CPU: the record names both
        platform = require_accelerator("bench_decode.py")
        try:
            res = run_fleet()
        except Exception as e:  # noqa: BLE001 - the record IS the channel
            print(json.dumps({
                "metric": "fleet_elasticity_cpu_smoke", "value": None,
                "unit": "replace_latency_s", "vs_baseline": None,
                "code_failure": True,
                "error": f"{type(e).__name__}: {e}"[:800]}))
            raise
        print(f"# fleet bench: scale_up={res['scale_up_latency_s']:.2f}s "
              f"replace={res['replace_latency_s']:.2f}s "
              f"ttft_p95 steady={res['ttft_p95_steady']:.4f}s "
              f"spike={res['ttft_p95_during_spike']:.4f}s "
              f"grow_decided={res['grow_decided']} "
              f"shed={res['spike_shed']}", file=sys.stderr)
        record = {"metric": "fleet_elasticity_cpu_smoke",
                  "value": res["replace_latency_s"],
                  "unit": "replace_latency_s", "vs_baseline": None,
                  "platform": platform, "replica_platform": "cpu", **res}
        print(json.dumps(record))
        # the gate: capacity loss and load spikes must both be answered
        # (a replacement decision actually restored strength; the spike
        # produced a grow decision and shed nothing)
        if not res["grow_decided"]:
            raise SystemExit("fleet gate failed: spike produced no grow "
                             "decision")
        if res["spike_shed"]:
            raise SystemExit(f"fleet gate failed: spike shed "
                             f"{res['spike_shed']} request(s)")
        return
    if args.spec_len > 0 and args.block_len != 1:
        ap.error("--spec-len replaces blocked decode; drop --block-len")
    if args.spec_auto and args.spec_len < 1:
        ap.error("--spec-auto tunes speculation per slot; give it a "
                 "ceiling with --spec-len N")
    if args.kv_page_policy != "uniform" and args.kv_layout != "paged":
        ap.error("--kv-page-policy hot_bf16 requires --kv-layout paged "
                 "(per-page refcounts decide which pages read as int8)")
    if args.tenants:
        if args.tenants < 1 or args.adapter_rank < 1:
            ap.error("--tenants and --adapter-rank must be >= 1")
        if args.block_len != 1:
            ap.error("--tenants drives the continuous batcher; drop "
                     "--block-len")
        if args.spec_auto:
            ap.error("--tenants and --spec-auto are separate protocols")

    # measure here, in this process, or fail here: no probe child, no
    # quiet CPU run. Only an explicit JAX_PLATFORMS=cpu gets the tiny
    # functional smoke below.
    tpu = require_accelerator("bench_decode.py") != "cpu"
    enable_compile_cache()

    from picotron_tpu.config import SMOLLM_1_7B, Config
    if tpu:
        model = dict(SMOLLM_1_7B)
        sizes = dict(slots=8, max_seq_len=1024, prompt_len=128, steps=256)
    else:  # JAX_PLATFORMS=cpu was asked for: the make *-smoke drive
        model = dict(
            name="tiny", num_hidden_layers=4, num_attention_heads=8,
            num_key_value_heads=8, hidden_size=256, intermediate_size=1024,
            vocab_size=4096, max_position_embeddings=2048, dtype="float32",
            attention_impl="sdpa")
        sizes = dict(slots=4, max_seq_len=128, prompt_len=16, steps=32)
    if args.spec_len > 0:
        # longer streams give greedy generation room to fall into the
        # repetitive attractors prompt-lookup drafting feeds on — the
        # regime this mode exists to measure (capped so prefill + warmup
        # rounds + the timed window fit the cache)
        sizes["steps"] = min(
            3 * sizes["steps"],
            sizes["max_seq_len"] - sizes["prompt_len"] - 1
            - SPEC_WARMUP_ROUNDS * (args.spec_len + 1))
        if sizes["steps"] < 1:
            ap.error(
                f"--spec-len {args.spec_len} leaves no timed decode window "
                f"inside max_seq_len {sizes['max_seq_len']} (prompt + "
                f"warmup rounds consume it); use a smaller draft length")
    cfg = Config.from_dict({
        "distributed": {"tp_size": 1},
        "model": model,
        "training": {"seq_length": sizes["max_seq_len"]},
        "dataset": {"name": "synthetic"},
    })
    accept = None
    auto = None
    tenancy = None
    try:
        if args.tenants:
            (tok_s, dpt, accept, kv_bytes, weight_bytes, engine,
             tenancy) = run_tenants(
                cfg, tenants=args.tenants,
                adapter_rank=args.adapter_rank,
                spec_len=args.spec_len, drafter=args.drafter,
                attend_impl=args.attend_impl,
                kv_layout=args.kv_layout,
                kv_page_policy=args.kv_page_policy,
                sample_on_device=args.sample_on_device,
                weight_dtype=args.weight_dtype, **sizes)
        elif args.spec_auto:
            (tok_s, dpt, accept, kv_bytes, weight_bytes, engine,
             auto) = run_spec_auto(
                cfg, spec_len=args.spec_len, drafter=args.drafter,
                attend_impl=args.attend_impl,
                kv_layout=args.kv_layout,
                kv_page_policy=args.kv_page_policy,
                sample_on_device=args.sample_on_device,
                weight_dtype=args.weight_dtype, **sizes)
        elif args.spec_len > 0:
            tok_s, dpt, accept, kv_bytes, weight_bytes, engine = run_spec(
                cfg, spec_len=args.spec_len, drafter=args.drafter,
                attend_impl=args.attend_impl,
                kv_layout=args.kv_layout,
                kv_page_policy=args.kv_page_policy,
                sample_on_device=args.sample_on_device,
                weight_dtype=args.weight_dtype, **sizes)
        else:
            tok_s, dpt, kv_bytes, weight_bytes, engine = run(
                cfg, block_len=args.block_len,
                attend_impl=args.attend_impl,
                kv_layout=args.kv_layout,
                kv_page_policy=args.kv_page_policy,
                sample_on_device=args.sample_on_device,
                weight_dtype=args.weight_dtype, **sizes)
    except Exception as e:  # noqa: BLE001 - the record IS the error channel
        print(json.dumps({
            "metric": BENCH_METRICS["bench_decode"], "value": None,
            "unit": "tokens/s/chip", "vs_baseline": None,
            "code_failure": True, "error": f"{type(e).__name__}: {e}"[:800]}))
        raise
    chips = engine.topo.world_size
    metric = (BENCH_METRICS["bench_decode"] if tpu
              else "decode_tokens_per_sec_cpu_smoke")
    print(f"# slots={sizes['slots']} prompt={sizes['prompt_len']} "
          f"steps={sizes['steps']} chips={chips} block_len={args.block_len} "
          f"spec_len={args.spec_len} attend_impl={args.attend_impl} "
          f"kv_layout={args.kv_layout} "
          f"kv_page_policy={args.kv_page_policy} "
          f"sample_on_device={args.sample_on_device} "
          + (f"accept_rate={accept:.3f} " if accept is not None else "")
          + f"dispatches/token={dpt:.3f} kv_bytes/token={kv_bytes} "
          f"weight_dtype={args.weight_dtype} weight_bytes={weight_bytes} "
          f"tokens/s={tok_s:.1f}",
          file=sys.stderr)
    logit_bytes = logits_bytes_to_host_per_token(
        engine, cfg.model.vocab_size, args.block_len, args.spec_len)
    record = {"metric": metric, "value": round(tok_s / chips, 1),
              "unit": "tokens/s/chip", "vs_baseline": None,
              "block_len": args.block_len,
              "dispatches_per_token": round(dpt, 4),
              "attend_impl": args.attend_impl,
              "kv_layout": args.kv_layout,
              "kv_page_policy": args.kv_page_policy,
              "sample_on_device": args.sample_on_device,
              "kv_bytes_per_token": kv_bytes,
              # the weight-side bytes story: the whole tree (int8 values
              # + scales included) and what one generated token costs in
              # weight HBM reads — every decode step streams all weights
              # once and emits one token per active slot, so per-token =
              # total / slots; speculative rounds amortize by emitting
              # ~1/dpt tokens per weight walk
              "weight_dtype": args.weight_dtype,
              "weight_bytes_total": weight_bytes,
              "weight_bytes_per_token": int(round(
                  weight_bytes * (dpt if args.spec_len > 0 else 1.0)
                  / sizes["slots"])),
              "logits_bytes_to_host_per_token": logit_bytes,
              # the per-rung A/B referee: dispatch-latency percentiles
              # from the PR 10 histograms, so flipping ONE flag (pipeline,
              # epilogue, policy) and diffing two JSON lines is the whole
              # measurement protocol on the chip. This is
              # the CANONICAL latency field — a projection of the same
              # registry instruments the "obs" snapshot below serializes,
              # so the two can never disagree at emit time.
              "dispatch_latency_s": dispatch_latency_summary(engine),
              # the device the numbers were taken on: the kv_bytes/
              # attend_impl deltas are layout facts and hold anywhere;
              # tokens/s only means hardware when this names one
              "device": device_record()}
    reg = engine.obs.registry
    if engine.paged is not None:
        # capacity story next to the bytes story: pool occupancy at the
        # end of the timed window + prefix-cache effectiveness (the bench
        # drives the engine directly, so hit rate is nonzero only for
        # workloads routed through the batcher's shared-prefix admission)
        p = engine.paged.stats()
        record.update(
            kv_page_len=p["kv_page_len"],
            kv_pages_total=p["kv_pages_total"],
            kv_pages_live=p["kv_pages_live"],
            kv_pages_quant=p["kv_pages_quant"],
            kv_pool_utilization=p["kv_pool_utilization"],
            prefix_hit_rate=p["prefix_hit_rate"])
        # ...and into the registry, so the obs snapshot below is complete
        reg.gauge("picotron_kv_pool_utilization").set(
            p["kv_pool_utilization"])
        reg.gauge("picotron_prefix_hit_rate").set(
            p["prefix_hit_rate"] or 0.0)
    if args.spec_len > 0:
        record["spec_len"] = args.spec_len
        record["drafter"] = args.drafter
        record["accept_rate"] = round(accept, 4)
        reg.gauge("picotron_accept_rate").set(accept)
    if auto is not None:
        # the controller story: converged per-slot draft lengths,
        # per-drafter accept split, per-regime dispatches/token, and
        # what the policy loop actually decided
        record["spec_auto"] = True
        record.update(auto)
    if tenancy is not None:
        # the multi-tenant story: per-tenant tokens/dpt/TTFT (+ accept
        # when speculating) and what streaming all live adapters costs
        # per decoded token next to the base weight bytes
        record.update(tenancy)
    # the engine registry's compact snapshot (dispatch count/latency
    # histograms, pool/accept gauges) rides along — one structured blob
    # instead of growing the hand-picked field list forever
    record["obs"] = reg.summary()
    print(json.dumps(record))


if __name__ == "__main__":
    main()
