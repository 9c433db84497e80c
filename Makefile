# picotron_tpu build/test entry points.
NATIVE_SO := picotron_tpu/native/_build/libpicotron_data.so
NATIVE_SRC := picotron_tpu/native/dataloader.cc

.PHONY: native test test-all test-isolated lint decode-smoke spec-smoke kernel-smoke quant-smoke paged-smoke chaos-smoke chaos-pod-smoke serve-smoke serve-chaos-smoke router-chaos-smoke tenant-smoke fleet-chaos-smoke obs-smoke clean

native: $(NATIVE_SO)

$(NATIVE_SO): $(NATIVE_SRC)
	mkdir -p $(dir $@)
	g++ -O3 -shared -fPIC -std=c++17 $< -o $@

# Fast gate: picolint first (pure-AST, ~1s — a lock-discipline or
# hot-path regression fails before any test imports jax), then the
# not-slow test matrix — ~6 min on one core. `make test-all` runs
# everything.
test: native lint
	python -m pytest tests/ -x -q -m "not slow"

test-all: native lint
	python -m pytest tests/ -x -q
	$(MAKE) obs-smoke
	$(MAKE) quant-smoke
	$(MAKE) router-chaos-smoke
	$(MAKE) tenant-smoke
	$(MAKE) fleet-chaos-smoke

# picolint static analysis (picotron_tpu/analysis/, docs/ANALYSIS.md):
# JAX hot-path rules (host syncs on traced values, trace-time
# nondeterminism, program_id-in-loop-body, jit-in-loop recompiles) +
# concurrency rules (lock-order inversions, blocking under a lock,
# unguarded shared mutation) over the whole package. Exit 1 on any
# finding not in analysis/baseline.json. `--json` variant for trends:
#   python -m picotron_tpu.tools.lint --json > lint.json
lint:
	python -m picotron_tpu.tools.lint --fail-on-new

# One pytest process per test file: the XLA CPU runtime's in-process
# collective rendezvous can abort the interpreter on rare races, and process
# isolation keeps one crash from taking down the rest of the suite.
test-isolated: native
	@fail=0; for f in tests/test_*.py; do \
	  echo "== $$f"; \
	  python -m pytest "$$f" -q || fail=1; \
	done; exit $$fail

# Serving-path smoke: tiny-model CPU generate through the full
# prefill/KV-cache/batcher/CLI stack (picotron_tpu/inference) — seconds,
# no checkpoint or network needed. Runs the blocked decode fast path
# (on-device stop state, one host sync per block) and the int8 KV cache.
decode-smoke:
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.generate --smoke
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.generate --smoke \
	  --kv-cache-dtype int8 --decode-block-len 4

# Speculative-decoding smoke: draft-verify generation (prompt-lookup
# drafter, one verify dispatch per accepted run) through the CLI. The
# accept rate, dispatches per token under 1 and the controller's
# convergence are pinned in tests/test_speculative.py.
spec-smoke:
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.generate --smoke \
	  --spec-len 4

# Flash-decode kernel parity (ops/pallas/decode_attention.py) in Pallas
# interpret mode on CPU: flash vs dense allclose across S=1 decode,
# speculative verify, chunked prefill; bf16/fp32 AND int8 caches; ragged
# lengths, stale rows, GQA down to nkv=1, non-dividing KV blocks;
# double-buffered DMA pinned bitwise against the serial fetch — plus the
# engine-level wiring proof for inference.attend_impl and the on-device
# sampling epilogue's seeded host-equivalence.
kernel-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_decode_kernel.py \
	  tests/test_sampling_epilogue.py -q

# Quantized-weights smoke (ops/pallas/quant_matmul.py, docs/INFERENCE.md
# "Quantized weights"): per-channel int8 weights through the full
# generate CLI with --check-weight-parity — greedy generations must be
# IDENTICAL to a bf16 engine fed the fake-quant reference (the
# quantization error is in both; any difference is the fused dequant
# pipeline itself), on tp=1 here and tp=1/2 in tier-1
# (tests/test_quant_weights.py). The serving default stays bf16, so
# decode/spec/paged-smoke output is unchanged.
quant-smoke:
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.generate --smoke \
	  --weight-dtype int8 --check-weight-parity
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.generate --smoke \
	  --weight-dtype int8 --check-weight-parity --kv-cache-dtype int8 \
	  --decode-block-len 4

# Paged-KV smoke (inference/paged_kv.py): a shared-prefix batch through
# the page-pool layout (block-table indirection, radix prefix sharing,
# copy-on-write) with --check-layout-parity asserting every request's
# tokens are IDENTICAL to the contiguous layout — fp32 and int8 caches.
# tests/test_paged_kv.py is the full tier-1 matrix.
paged-smoke:
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.generate --smoke \
	  --kv-layout paged --check-layout-parity \
	  --prompt-ids "1,2,3,4,5,6,7,8,9,10,11,12,13,14,15,16,17,18" \
	  --prompt-ids "1,2,3,4,5,6,7,8,9,10,11,12,13,14,21,22" \
	  --prompt-ids "1,2,3,4,5,6,7,8,9,10,11,12,13,14,15,16,31"
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.generate --smoke \
	  --kv-layout paged --check-layout-parity --kv-cache-dtype int8 \
	  --decode-block-len 4 \
	  --prompt-ids "1,2,3,4,5,6,7,8,9,10,11,12,13,14,15,16,17,18" \
	  --prompt-ids "1,2,3,4,5,6,7,8,9,10,11,12,13,14,21,22"

# Fault-injection suite on a CPU mesh (picotron_tpu/resilience/): chaos
# SIGTERM/crash/NaN/truncation at fixed steps, kill->resume bit-for-bit
# equivalence, corrupt-checkpoint fallback, supervisor restart bounds.
chaos-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py -q

# Pod-scale chaos drills (resilience/cluster.py, docs/MULTIHOST.md): a
# REAL 2-process jax.distributed CPU pod under tools/supervise.py
# --num-procs. Chaos-preempt one rank -> preemption consensus takes the
# same coordinated emergency save on both ranks (75/75, no hang) and the
# relaunch resumes bit-for-bit; chaos-SIGKILL one rank -> the peer's
# cluster monitor exits 77 within peer_timeout_s instead of wedging in
# gloo, and the pod restarts together. A few minutes (pytest.mark.slow;
# the fast consensus/monitor units are tier-1 in tests/test_cluster.py).
chaos-pod-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_cluster_pod.py -q

# HTTP serving front end smoke (tools/serve.py, docs/SERVING.md): start
# the server on an ephemeral port with the tiny CPU model, check
# /healthz //readyz, POST one request, stream a second, then SIGTERM —
# the in-flight request finishes, the drain is clean, and every counter
# accounts. Exits nonzero on any malfunction.
serve-smoke:
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.serve --smoke

# Observability smoke (picotron_tpu/obs, docs/OBSERVABILITY.md): the
# serve smoke drive with its telemetry checks — /metrics agreeing with
# /statz, a timed /profilez capture — saving the drive's /tracez JSON,
# then tools/trace_dump.py re-validates the saved trace from scratch and
# requires a COMPLETE parented request chain (queue_wait -> prefill ->
# every dispatch -> delivery). Runs inside `make test-all`.
OBS_SMOKE_DIR := /tmp/picotron-obs-smoke
obs-smoke:
	rm -rf $(OBS_SMOKE_DIR) $(OBS_SMOKE_DIR)-overlap
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.serve --smoke \
	  --obs-dump $(OBS_SMOKE_DIR)
	python -m picotron_tpu.tools.trace_dump $(OBS_SMOKE_DIR)/trace.json \
	  --require-request-chain
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.serve --smoke \
	  --overlap --obs-dump $(OBS_SMOKE_DIR)-overlap
	python -m picotron_tpu.tools.trace_dump \
	  $(OBS_SMOKE_DIR)-overlap/trace.json \
	  --require-request-chain --require-overlap-chain

# Multi-replica router chaos drill (tools/router.py, docs/SERVING.md
# "Multi-replica fabric"): 3 in-process serve.py replicas behind the
# prefix-affinity router; kill one mid-stream (the spliced client stream
# must be BIT-IDENTICAL to an unfaulted greedy run, replays=1, no token
# duplicated or dropped), flap/stall a second through the circuit
# breaker's open -> half-open -> closed walk with zero client-visible
# errors, inject scrape failures (candidate drop without a breaker
# trip), drain a third gracefully — with every request accounted in the
# router's own /metrics and a route -> attempt[n] -> replay span chain
# in /tracez. The same drill runs in tier-1 (tests/test_router.py).
router-chaos-smoke:
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.router --smoke

# Multi-tenant serving smoke (ISSUE 16, inference/tenancy.py,
# docs/SERVING.md "Multi-tenant serving"): the adapter-parity gate —
# greedy generations through the segmented multi-LoRA matmul must be
# IDENTICAL to an adapter-less engine fed the merged-weight (W + BA)
# reference — on the int8 base (the fake-quant error is in both; any
# difference is the segmented adapter path itself), then on the paged
# layout under speculation. Several tenants in ONE continuous batch are
# tier-1's (tests/test_tenancy.py). The serving default stays
# adapter-less, so every other smoke's output is unchanged.
tenant-smoke:
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.generate --smoke \
	  --weight-dtype int8 --adapter 4 --check-adapter-parity
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.generate --smoke \
	  --adapter 4:7:0.5 --check-adapter-parity --kv-layout paged \
	  --spec-len 3

# Elastic fleet chaos drill (ISSUE 17, tools/fleet.py, docs/SERVING.md
# "Elastic fleet"): the controller bootstraps a 3-worker fleet against
# an EMPTY router through the dynamic replica-set admin API, then the
# acceptance drill under live traffic — SIGKILL a worker holding an
# in-flight stream (the fleet replaces it within the restart-budget
# ladder while the router replays the stream exactly-once, greedy
# bit-identical), stall the controller's scrape plane (stale must never
# read as dead: no replacement storm), inject an admission spike (a grow
# decision within the cooloff window, zero requests shed), then the
# scale-down drain back to min_workers (zero in-flight lost, hot radix
# prefixes relocated to a survivor, replica deregistered) — with every
# decision accounted in picotron_fleet_* counters. Exits nonzero on any
# malfunction.
fleet-chaos-smoke:
	JAX_PLATFORMS=cpu python -m picotron_tpu.tools.fleet --smoke

# Serving chaos suite (tests/test_serving.py): dispatch-exception,
# latency-spike, and poisoned-logits faults through the engine hooks —
# no hangs, every submitted request terminates with an accounted
# finish_reason (eos|length|timeout|shed|error), unaffected requests are
# bit-identical to a chaos-off run; plus slot-failure isolation, the
# flash->dense degradation ladder, admission control, and drain.
serve-chaos-smoke:
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py -q

clean:
	rm -rf picotron_tpu/native/_build
