"""Text generation CLI — the serving path end-to-end.

    python -m picotron_tpu.tools.generate --config exp.json \
        --load-path checkpoints --prompt-ids 5,276,388 --max-new-tokens 64

Weights come from one of:
  --load-path    orbax training checkpoint dir (params-only restore;
                 pp/interleave-trained stacks are remapped to the engine's
                 contiguous layout at load — checkpoint.load_params)
  --hf-path      HF-format safetensors file/dir (checkpoint.load_hf_safetensors)
  --random-init  seed-derived random weights (plumbing smoke runs)

Prompts are repeatable --prompt-ids (comma-separated token ids — works
air-gapped) or repeatable --prompt (text; needs the transformers tokenizer
for model.name). All prompts run through one ContinuousBatcher, so a mixed
batch exercises admission, slot recycling, and per-request sampling params.

``--smoke`` is the `make decode-smoke` target: a built-in tiny CPU model
with random weights generates from fixed prompts in seconds and exits
nonzero on any malfunction — no config, checkpoint, or network needed.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from functools import partial
from typing import Optional

SMOKE_CONFIG = {
    "distributed": {"tp_size": 1, "use_cpu": True},
    "model": dict(
        name="tiny-smoke", num_hidden_layers=4, num_attention_heads=8,
        num_key_value_heads=4, hidden_size=64, intermediate_size=128,
        vocab_size=256, max_position_embeddings=128, dtype="float32",
        attention_impl="sdpa"),
    "training": {"seq_length": 64},
    "dataset": {"name": "synthetic"},
}


def _load_weights(args, cfg, engine):
    """Resolve --load-path / --hf-path / --random-init to sharded params.
    An ``engine`` built with ``weight_dtype="int8"`` gets the per-channel
    quantized tree: the HF path quantizes as it streams off the file,
    the orbax path quantizes off the restore, the random-init path
    quantizes the fresh tree — all three land as the same
    ``{"q", "s"}``-leaf form the engine's matmul sites dispatch on."""
    import jax

    from picotron_tpu import checkpoint as ckpt
    from picotron_tpu.models import llama, model_module
    from picotron_tpu.topology import named_shardings

    quant = getattr(engine, "quant_weights", False)
    wdt = "int8" if quant else "bf16"
    model = model_module(cfg.model)
    if model is not llama and (args.hf_path or args.load_path):
        raise SystemExit(
            f"model_type {cfg.model.model_type!r} is served from seeded "
            "weights only (--random-init): the checkpoint readers know "
            "the Llama tree")
    if args.hf_path:
        return ckpt.load_hf_safetensors(args.hf_path, cfg.model, engine.topo,
                                        weight_dtype=wdt)
    if args.load_path:
        # the restore is SHARDED for both weight formats (checkpoints
        # store dense, so the dense pspecs describe what orbax reads);
        # the int8 path then quantizes leaf by leaf on the sharded tree
        # — sharding, not donation, is what keeps a big model's dense
        # tree and fp32 quantization transients off any single device
        # (llama.quantize_params explains why donation is rejected)
        like = jax.eval_shape(partial(llama.init_params, m=cfg.model),
                              jax.random.PRNGKey(0))
        shardings = named_shardings(engine.topo,
                                    llama.param_pspecs(cfg.model))
        like = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            like, shardings)
        mgr = ckpt.CheckpointManager(
            args.load_path, mirror_dir=cfg.resilience.ckpt_mirror_dir)
        params, step, tokens = mgr.load_params(
            like, layout=(cfg.model.num_hidden_layers, 1), weight_dtype=wdt)
        mgr.close()
        print(f"loaded step {step} ({tokens} trained tokens) "
              f"from {args.load_path}")
        return engine.shard_params(params) if quant else params
    params = jax.jit(lambda k: model.init_params(k, cfg.model))(
        jax.random.PRNGKey(args.seed))
    if quant:
        params = llama.quantize_params(params)
    return engine.shard_params(params)


def _build_requests(args, tokenizer) -> list:
    from picotron_tpu.inference import Request

    prompts = []
    for spec in args.prompt_ids or ():
        prompts.append([int(t) for t in spec.replace(" ", "").split(",") if t])
    for text in args.prompt or ():
        prompts.append(list(tokenizer(text)["input_ids"]))
    if not prompts:
        raise SystemExit("no prompts: pass --prompt-ids and/or --prompt")
    return [
        Request(uid=f"req{i}", prompt=p, max_new_tokens=args.max_new_tokens,
                temperature=args.temperature, top_k=args.top_k,
                top_p=args.top_p, eos_id=args.eos_id)
        for i, p in enumerate(prompts)
    ]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="generate from a picotron-tpu checkpoint "
                    "(continuous-batched KV-cache decode)")
    ap.add_argument("--config", help="training config.json (model shape, tp)")
    ap.add_argument("--load-path", default="", help="orbax checkpoint dir")
    ap.add_argument("--hf-path", default="", help="HF safetensors file/dir")
    ap.add_argument("--random-init", action="store_true",
                    help="seed-derived random weights (plumbing smoke)")
    ap.add_argument("--prompt-ids", action="append",
                    help="comma-separated token ids (repeatable)")
    ap.add_argument("--prompt", action="append",
                    help="text prompt (repeatable; needs the HF tokenizer)")
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy")
    ap.add_argument("--top-k", type=int, default=0, help="<= 0 disables")
    ap.add_argument("--top-p", type=float, default=1.0, help=">= 1 disables")
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode batch width (engine slots)")
    ap.add_argument("--max-seq-len", type=int, default=None)
    ap.add_argument("--decode-block-len", type=int, default=None,
                    help="decode steps fused per dispatch (default: "
                         "config inference.decode_block_len; 1 = per-token "
                         "loop)")
    ap.add_argument("--kv-cache-dtype", choices=["auto", "int8"],
                    default=None,
                    help="KV cache storage (default: config "
                         "inference.kv_cache_dtype; int8 = quantized "
                         "cache, ~2x slots/context per HBM byte)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill width for prompts longer than "
                         "this (default: config inference.prefill_chunk)")
    ap.add_argument("--spec-len", type=int, default=None,
                    help="speculative decoding: draft tokens per verify "
                         "dispatch (default: config inference.spec_len; "
                         "0 = off)")
    ap.add_argument("--spec-ngram", type=int, default=None,
                    help="longest suffix n-gram the prompt-lookup drafter "
                         "matches (default: config inference.spec_ngram)")
    ap.add_argument("--kv-layout", choices=["contiguous", "paged"],
                    default=None,
                    help="KV cache layout (default: config "
                         "inference.kv_layout; paged = block-table pool "
                         "with refcounted prefix sharing + COW)")
    ap.add_argument("--kv-page-policy", choices=["uniform", "hot_bf16"],
                    default=None,
                    help="per-page storage policy (paged layout only; "
                         "default: config inference.kv_page_policy) — "
                         "hot_bf16 reads radix-shared prefix pages at "
                         "full precision, exclusive tails as int8")
    ap.add_argument("--weight-dtype", choices=["bf16", "int8"],
                    default=None,
                    help="weight storage (default: config "
                         "inference.weight_dtype) — int8 = per-channel "
                         "quantized matmul weights served through the "
                         "fused dequant matmul, ~half the bf16 bytes")
    ap.add_argument("--check-weight-parity", action="store_true",
                    help="run the batch again on a bf16 engine fed the "
                         "FAKE-QUANT reference (dequantized int8 weights "
                         "through the dense matmul) and fail unless every "
                         "request's tokens match — the `make quant-smoke` "
                         "gate proving the fused int8 pipeline implements "
                         "fake-quant semantics exactly")
    ap.add_argument("--adapter", default=None,
                    metavar="RANK[:SEED[:SCALE]]",
                    help="serve every request through one seed-derived "
                         "LoRA adapter (tenancy.AdapterPack, slot 1) "
                         "over the base weights — the multi-tenant "
                         "segmented dispatch with a single tenant")
    ap.add_argument("--check-adapter-parity", action="store_true",
                    help="run the batch again on an adapter-less dense "
                         "engine fed the MERGED reference (W + A @ B, "
                         "llama.merge_adapter; an int8 primary merges "
                         "into its fake-quant dense twin) and fail "
                         "unless every request's tokens match — the "
                         "`make tenant-smoke` gate proving the "
                         "segmented adapter matmul implements "
                         "merged-weight semantics exactly (greedy-only, "
                         "same exactness rule as --check-weight-parity)")
    ap.add_argument("--sample-on-device", action="store_true",
                    help="fused sampling epilogue: prefill/decode "
                         "dispatches sample inside the jitted program "
                         "and ship token ids, never [B, vocab] logits "
                         "(seeded-identical to the host sampler)")
    ap.add_argument("--check-layout-parity", action="store_true",
                    help="run the batch again under the OTHER kv layout "
                         "and fail unless every request's tokens match — "
                         "the `make paged-smoke` equivalence gate")
    ap.add_argument("--smoke", action="store_true",
                    help="built-in tiny CPU model + random init + fixed "
                    "prompts (the `make decode-smoke` target)")
    args = ap.parse_args(argv)

    from picotron_tpu.config import Config
    from picotron_tpu.train import _ensure_devices

    if args.smoke:
        cfg = Config.from_dict(SMOKE_CONFIG)
        args.random_init = True
        if not args.prompt_ids and not args.prompt:
            args.prompt_ids = ["1,2,3,4,5,6,7,8", "9,10,11", "12,13,14,15,16"]
        args.max_new_tokens = min(args.max_new_tokens, 16)
    elif args.config:
        with open(args.config) as f:
            cfg = Config.from_dict(json.load(f))
    else:
        ap.error("pass --config (or --smoke)")
    if not (args.load_path or args.hf_path or args.random_init):
        ap.error("pass one of --load-path / --hf-path / --random-init")
    _ensure_devices(cfg)
    from picotron_tpu.utils import enable_compile_cache

    enable_compile_cache()  # before the first compile

    from picotron_tpu.inference import ContinuousBatcher, InferenceEngine

    tokenizer = None
    if args.prompt:
        from transformers import AutoTokenizer

        tokenizer = AutoTokenizer.from_pretrained(cfg.model.name)

    if args.kv_cache_dtype is not None:
        cfg.inference.kv_cache_dtype = args.kv_cache_dtype
    if args.kv_layout is not None:
        cfg.inference.kv_layout = args.kv_layout
    if args.kv_page_policy is not None:
        cfg.inference.kv_page_policy = args.kv_page_policy
    if args.sample_on_device:
        cfg.inference.sample_on_device = True
    if args.weight_dtype is not None:
        cfg.inference.weight_dtype = args.weight_dtype
    if args.check_weight_parity and cfg.inference.weight_dtype != "int8":
        ap.error("--check-weight-parity compares int8 against the "
                 "fake-quant reference; pass --weight-dtype int8")
    if args.check_weight_parity and args.temperature != 0.0:
        # the gate's contract is token IDENTITY, which only greedy decode
        # guarantees: the fused and dense matmuls agree to allclose, not
        # bitwise, so a seeded categorical draw can flip at a near-tie —
        # same exactness rule as --check-layout-parity's hot_bf16 guard
        ap.error("--check-weight-parity is a greedy-only gate (fused vs "
                 "dense logits are allclose, not bit-equal; sampling can "
                 "flip at near-ties); drop --temperature")
    if args.check_adapter_parity and args.adapter is None:
        ap.error("--check-adapter-parity compares the segmented adapter "
                 "dispatch against its merged-weight oracle; pass "
                 "--adapter RANK[:SEED[:SCALE]]")
    if args.check_adapter_parity and args.temperature != 0.0:
        ap.error("--check-adapter-parity is a greedy-only gate (segmented "
                 "vs merged logits are allclose, not bit-equal; sampling "
                 "can flip at near-ties); drop --temperature")
    if args.check_weight_parity and args.adapter is not None:
        ap.error("--check-weight-parity's reference engine is "
                 "adapter-less; run it without --adapter (adapter "
                 "correctness has its own gate, --check-adapter-parity)")
    adapter_rank, adapter_seed, adapter_scale = 0, 0, None
    if args.adapter is not None:
        from picotron_tpu.inference import tenancy as _tenancy

        parts = str(args.adapter).split(":")
        try:
            adapter_rank = int(parts[0])
            adapter_seed = int(parts[1]) if len(parts) > 1 else 0
            adapter_scale = (float(parts[2]) if len(parts) > 2
                             else _tenancy.DEFAULT_ADAPTER_SCALE)
        except ValueError as e:
            ap.error(f"bad --adapter spec {args.adapter!r} "
                     f"(want RANK[:SEED[:SCALE]]): {e}")
        if adapter_rank < 1:
            ap.error("--adapter rank must be >= 1")
    if args.check_layout_parity and cfg.inference.kv_page_policy != "uniform":
        # checked on the EFFECTIVE config (flag or config file): mixed
        # pages quantize cold tails, so contiguous-vs-paged would be
        # allclose, not token-equal — the parity gate is a uniform check
        ap.error("--check-layout-parity needs kv_page_policy 'uniform' "
                 "(hot_bf16 int8 tails make parity allclose, not exact)")
    t0 = time.perf_counter()
    adapters = adapter_leaves = None
    if args.adapter is not None:
        adapters = _tenancy.AdapterPack(cfg.model, slots=2,
                                        rank=adapter_rank)
        adapter_leaves = adapters.random_leaves(
            adapter_rank, adapter_seed, adapter_scale)
        adapters.set_slot(1, adapter_leaves)
    engine = InferenceEngine(cfg, slots=args.slots,
                             max_seq_len=args.max_seq_len,
                             decode_block_len=args.decode_block_len,
                             prefill_chunk=args.prefill_chunk,
                             spec_len=args.spec_len,
                             spec_ngram=args.spec_ngram,
                             adapters=adapters)
    params = _load_weights(args, cfg, engine)
    requests = _build_requests(args, tokenizer)
    if adapters is not None:
        for r in requests:
            r.adapter_slot = 1
    setup_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    batcher = ContinuousBatcher(engine, params, seed=args.seed)
    results = batcher.run(requests)
    gen_s = time.perf_counter() - t0

    if args.check_weight_parity:
        # same batch, same seed, a bf16 engine fed the FAKE-QUANT
        # reference (quantize -> dequantize through the dense matmul):
        # every request's tokens must match exactly. The quantization
        # error is identical on both sides, so any difference is the
        # fused int8 pipeline itself (kernel/fallback, scale sharding,
        # dispatch wiring) — the weight-side counterpart of
        # --check-layout-parity's equivalence gate.
        import jax.numpy as jnp

        from picotron_tpu.models import llama

        eng2 = InferenceEngine(cfg, slots=args.slots,
                               max_seq_len=args.max_seq_len,
                               decode_block_len=args.decode_block_len,
                               prefill_chunk=args.prefill_chunk,
                               spec_len=args.spec_len,
                               spec_ngram=args.spec_ngram,
                               weight_dtype="bf16")
        dense = _load_weights(args, cfg, eng2)
        fakeq = llama.dequantize_params(llama.quantize_params(dense),
                                        jnp.dtype(cfg.model.dtype))
        results2 = ContinuousBatcher(
            eng2, eng2.shard_params(fakeq), seed=args.seed,
        ).run(_build_requests(args, tokenizer))
        bad = [u for u in results if results[u].tokens != results2[u].tokens]
        if bad:
            print(f"FAILED: weight parity mismatch (int8 vs fake-quant "
                  f"bf16) for {bad}", file=sys.stderr)
            return 1
        print(f"weight parity: int8 == fake-quant reference for "
              f"{len(results)} requests")

    if args.check_adapter_parity:
        # same batch, same seed, an ADAPTER-LESS dense engine fed the
        # merged tree W + A @ B (llama.merge_adapter): every request's
        # tokens must match exactly. The segmented gather (per-row A/B
        # pair through the lora matmul, residual added before the tp
        # collective) and the merged matmul compute the same values to
        # fp32 tolerance; greedy pins the tokens. An int8 primary merges
        # into its FAKE-QUANT dense twin — the same reference recipe as
        # --check-weight-parity, so one run gates both the adapter path
        # and its int8 composition.
        import jax.numpy as jnp

        from picotron_tpu.models import llama

        eng2 = InferenceEngine(cfg, slots=args.slots,
                               max_seq_len=args.max_seq_len,
                               decode_block_len=args.decode_block_len,
                               prefill_chunk=args.prefill_chunk,
                               spec_len=args.spec_len,
                               spec_ngram=args.spec_ngram,
                               weight_dtype="bf16")
        dense = _load_weights(args, cfg, eng2)
        if engine.weight_dtype == "int8":
            dense = llama.dequantize_params(
                llama.quantize_params(dense), jnp.dtype(cfg.model.dtype))
        merged = llama.merge_adapter(dense, adapter_leaves)
        results2 = ContinuousBatcher(
            eng2, eng2.shard_params(merged), seed=args.seed,
        ).run(_build_requests(args, tokenizer))
        bad = [u for u in results if results[u].tokens != results2[u].tokens]
        if bad:
            print(f"FAILED: adapter parity mismatch (segmented vs "
                  f"merged-weight reference) for {bad}", file=sys.stderr)
            return 1
        print(f"adapter parity: segmented adapter == merged-weight "
              f"reference for {len(results)} requests "
              f"(rank={adapter_rank}, weights={engine.weight_dtype})")

    if args.check_layout_parity:
        # same batch, same seed/weights, the OTHER cache layout: every
        # request's token stream must match exactly (the paged layout's
        # equivalence gate — prefix sharing and COW must be invisible in
        # the output)
        other = ("contiguous" if engine.kv_layout == "paged" else "paged")
        eng2 = InferenceEngine(cfg, slots=args.slots,
                               max_seq_len=args.max_seq_len,
                               decode_block_len=args.decode_block_len,
                               prefill_chunk=args.prefill_chunk,
                               spec_len=args.spec_len,
                               spec_ngram=args.spec_ngram,
                               kv_layout=other,
                               # hot_bf16 is defined over pool pages; the
                               # contiguous side of the parity pair runs
                               # uniform (and the comparison is only run
                               # with a uniform primary — mixed tails
                               # quantize, parity would be allclose not ==)
                               kv_page_policy="uniform")
        results2 = ContinuousBatcher(
            eng2, _load_weights(args, cfg, eng2), seed=args.seed,
        ).run(_build_requests(args, tokenizer))
        bad = [u for u in results
               if results[u].tokens != results2[u].tokens]
        if bad:
            print(f"FAILED: layout parity mismatch "
                  f"({engine.kv_layout} vs {other}) for {bad}",
                  file=sys.stderr)
            return 1
        print(f"layout parity: {engine.kv_layout} == {other} for "
              f"{len(results)} requests")

    n_tokens = 0
    failed = False
    for req in requests:
        r = results[req.uid]
        n_tokens += len(r.tokens)
        ok = (len(r.tokens) > 0
              and all(0 <= t < cfg.model.vocab_size for t in r.tokens))
        failed |= not ok
        line = (f"[{r.uid}] prompt={r.prompt} -> {r.tokens} "
                f"({r.finish_reason})")
        if tokenizer is not None:
            line += f"\n  text: {tokenizer.decode(r.prompt + r.tokens)!r}"
        print(line)
    dpt = batcher.decode_dispatches / max(batcher.generated_tokens, 1)
    spec = (f"spec={engine.spec_len} "
            f"accept={batcher.accept_rate:.2f} " if engine.spec_len > 0
            and batcher.accept_rate is not None else "")
    print(f"{n_tokens} tokens in {gen_s:.2f}s "
          f"({n_tokens / max(gen_s, 1e-9):.1f} tok/s, "
          f"setup {setup_s:.1f}s, slots={engine.slots}, "
          f"tp={engine.topo.tp_size}, block={engine.decode_block_len}, "
          f"kv={'int8' if engine.quantized else str(engine.cache_dtype)}, "
          f"weights={engine.weight_dtype}, "
          f"{spec}{batcher.decode_dispatches} decode dispatches = "
          f"{dpt:.3f}/token)")
    if failed:
        print("FAILED: some request produced no/invalid tokens",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
