"""The jitted 4D-parallel training step.

One ``shard_map`` over the ('dp','pp','cp','tp') mesh contains the whole step:
pipeline schedule (or plain grad-accumulation when pp=1), TP/CP collectives
inside the model, the dp×cp gradient psum, and the optimizer update. This is
the TPU-native collapse of the reference's layered runtime — train_step
(train.py:29-55), the schedule dispatch (train.py:223-231), DataParallelBucket
(data_parallel.py:62-170 + bucket.py), and the optimizer step (train.py:235) —
into a single compiled program. Bucketing dissolves: XLA's scheduler overlaps
the gradient all-reduce with remaining backward compute, which is what the
25 MB buckets + async NCCL achieved by hand.

Gradient sync semantics preserved from the reference:
- grads are averaged over the fused dp×cp group (data_parallel.py:47,83);
- accumulation happens in fp32, cast to the param dtype before the update
  (main_grad policy, data_parallel.py:66,81,161-165);
- sync happens once per step, after the last microbatch
  (require_backward_grad_sync, train.py:40-41).
Additionally, grads of pp-replicated params (embedding, final norm, LM head)
are psum'd over 'pp' — only the owning stage produces nonzero contributions.
"""

from __future__ import annotations

import sys
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.tree_util import tree_flatten_with_path, tree_unflatten

from picotron_tpu.config import Config
from picotron_tpu.models import llama
from picotron_tpu.parallel.pp import (
    no_pipeline,
    pipeline_1f1b,
    pipeline_1f1b_interleaved,
    pipeline_afab,
)
from picotron_tpu.parallel.tp import (
    all_gather_dim_invariant,
    reduce_scatter_dim,
)
from picotron_tpu.topology import Topology, batch_pspec, named_shardings
from picotron_tpu.utils import (
    log0,
    shard_map as shard_map_compat,
    typeof_vma,
)


def lr_schedule(t):
    """Learning-rate schedule from the training config: optional linear
    warmup from 0 over ``lr_warmup_steps``, then constant / cosine / linear
    decay to ``learning_rate * lr_min_ratio`` over ``lr_decay_steps``
    (default total_train_steps). Returns a plain float for the default
    (constant, no warmup) so the optimizer state keeps the schedule-free
    structure. Beyond the reference, which trains at constant lr
    (train.py:209)."""
    peak = t.learning_rate
    w = t.lr_warmup_steps
    if t.lr_schedule == "constant" and w == 0:
        return peak
    total = t.lr_decay_steps if t.lr_decay_steps is not None else t.total_train_steps
    end = peak * t.lr_min_ratio
    if t.lr_schedule == "constant":
        return optax.join_schedules(
            [optax.linear_schedule(0.0, peak, w),
             optax.constant_schedule(peak)], [w])
    if t.lr_schedule == "cosine":
        return optax.warmup_cosine_decay_schedule(
            0.0, peak, w, max(total, w + 1), end)
    return optax.join_schedules(
        [optax.linear_schedule(0.0, peak, w),
         optax.linear_schedule(peak, end, max(total - w, 1))], [w])


def build_optimizer(cfg: Config) -> optax.GradientTransformation:
    """AdamW with torch defaults (reference train.py:209) and the configured
    lr schedule. Gradient clipping is NOT part of the chain: inside shard_map
    optax.clip_by_global_norm would compute each device's *local* norm —
    different per tp/pp shard, which desyncs replicated params. The step
    applies ``clip_by_global_norm_sharded`` instead (true global norm via
    per-leaf psum over the axes that shard it)."""
    t = cfg.training
    # chain() wrapper kept so the optimizer-state pytree structure matches
    # checkpoints saved when clipping lived inside the chain (grad_clip=0
    # runs — the default — share the (adamw_state,) structure; clip>0
    # checkpoints from before the sharded-clip change need a fresh opt state)
    return optax.chain(optax.adamw(
        lr_schedule(t), b1=t.adam_beta1, b2=t.adam_beta2, eps=t.adam_eps,
        weight_decay=t.weight_decay,
    ))


def _spec_axes(spec) -> tuple:
    axes = []
    for entry in spec:
        if entry is None:
            continue
        axes.extend([entry] if isinstance(entry, str) else list(entry))
    return tuple(axes)


def global_sq_norm_sharded(tree, pspecs):
    """True global squared norm of a sharded tree: each leaf's squared sum
    is psum'd over exactly the axes that shard it (replicated axes excluded
    so nothing is double-counted), so every device computes the same scalar.
    Works for both the param-shaped grad tree (pspecs = llama.param_pspecs)
    and the ZeRO-1 chunk tree (pspecs = zero1_chunk_specs). Shared by the
    global-norm clip and the non-finite gate (any NaN/Inf anywhere in the
    tree — even on a single shard — poisons the psum'd total on EVERY
    device, which is what makes the gate's select globally consistent)."""
    spec_leaves = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
    total = jnp.float32(0.0)
    for g, spec in zip(jax.tree.leaves(tree), spec_leaves):
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        axes = _spec_axes(spec)
        if axes:
            sq = lax.psum(sq, axes)
        total = total + sq
    return total


def clip_by_global_norm_sharded(grads, pspecs, max_norm):
    """Mesh-aware global-norm clip, matching optax.clip_by_global_norm
    numerics on a single device and keeping replicated params in sync on
    any topology (see global_sq_norm_sharded)."""
    gn = jnp.sqrt(global_sq_norm_sharded(grads, pspecs))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-16))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads)


# --------------------------------------------------------------------------- #
# ZeRO-1: dp-sharded optimizer state (beyond-parity; SURVEY §2.3 marks ZeRO
# out of the reference's scope). Each param leaf's local (pp/tp-sharded)
# block is flattened, zero-padded to a multiple of dp, and split into dp
# equal chunks; gradients arrive by reduce-scatter (instead of all-reduce),
# AdamW updates only the local chunk, and the updated chunks all-gather back
# into full params. State memory per device drops by dp at identical
# numerics (pad entries have zero grad and zero param, so their AdamW update
# is exactly zero).
# --------------------------------------------------------------------------- #


def _zero1_chunk_len(n: int, dp: int) -> int:
    return -(-n // dp)


def zero1_chunk_specs(pspecs):
    """PartitionSpec for each flattened chunk leaf: one dimension, tiled over
    'dp' plus every axis that shards the param leaf (canonical order: dp
    outermost, then the param spec's axes in order)."""
    return jax.tree.map(lambda spec: P(("dp", *_spec_axes(spec))), pspecs,
                        is_leaf=lambda x: isinstance(x, P))


def _zero1_scatter(g, dp):
    """Reduce-scatter a local grad block over 'dp': [shape] -> mean chunk
    [ceil(n/dp)]."""
    n = g.size
    c = _zero1_chunk_len(n, dp)
    flat = jnp.pad(g.reshape(-1), (0, dp * c - n))
    return reduce_scatter_dim(flat, "dp", 0) / dp


def _zero1_slice(p, dp):
    """This dp rank's chunk of a local param block."""
    n = p.size
    c = _zero1_chunk_len(n, dp)
    flat = jnp.pad(p.reshape(-1), (0, dp * c - n))
    return lax.dynamic_slice_in_dim(flat, lax.axis_index("dp") * c, c, 0)


def _zero1_unsplit(chunk, like):
    """All-gather updated chunks over 'dp' back into the full local block.
    The invariant-typed gather is what lets the updated params flow back
    out through dp-less out_specs under ``check_vma``; on the checker-off
    build it is the plain public all_gather (see all_gather_dim_invariant)."""
    full = all_gather_dim_invariant(chunk, "dp", 0)
    return full[: like.size].reshape(like.shape)


def zero1_opt_pspecs(cfg: Config, optimizer, pspecs):
    """PartitionSpecs of the dp-chunked optimizer state: eval-shape the
    optimizer on local-chunk-shaped params, then map mu/nu leaves to their
    chunk specs by path suffix (scalars like count stay replicated)."""
    dp = cfg.distributed.dp_size
    p_shape = jax.eval_shape(
        partial(llama.init_params, m=cfg.model, pp_size=cfg.distributed.pp_size,
                interleave=cfg.distributed.pp_interleave),
        jax.random.PRNGKey(0))
    chunk_shape = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct((_zero1_chunk_len(p.size, dp),), p.dtype),
        p_shape)
    o_shape = jax.eval_shape(optimizer.init, chunk_shape)
    return opt_pspecs(o_shape, zero1_chunk_specs(pspecs))


def _key_name(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def opt_pspecs(opt_state_shape, pspecs) -> Any:
    """PartitionSpecs for the optimizer state: any leaf whose tree path ends
    with a parameter's path inherits that parameter's spec (optax mu/nu mirror
    the param tree); scalars (e.g. count) are replicated."""
    is_p = lambda x: isinstance(x, P)
    pflat = tree_flatten_with_path(pspecs, is_leaf=is_p)[0]
    by_path = {tuple(_key_name(k) for k in path): spec for path, spec in pflat}
    oflat, otree = tree_flatten_with_path(opt_state_shape)
    out = []
    for path, leaf in oflat:
        keys = tuple(_key_name(k) for k in path)
        spec = P()
        for i in range(len(keys)):
            if keys[i:] in by_path:
                spec = by_path[keys[i:]]
                break
        out.append(spec)
    return tree_unflatten(otree, out)


def sync_sp_norm_grads(grads):
    """Sequence parallelism: norm-weight grads are partial sums over each tp
    rank's seq shard (the norms run on sharded activations) — psum over 'tp'
    completes them. Matmul weight grads are already correct: their activation
    operands are all-gathered to full sequence inside the layer."""
    g = dict(grads)
    layers = dict(g["layers"])
    for k in ("attn_norm", "mlp_norm"):
        layers[k] = lax.psum(layers[k], "tp")
    g["layers"] = layers
    g["final_norm"] = lax.psum(g["final_norm"], "tp")
    return g


def sync_pp_replicated_grads(grads, pspecs):
    """psum over 'pp' for grads of params replicated across stages (embedding,
    final norm, LM head): only the owning stage contributes nonzero grads."""
    flat_g, tree_g = jax.tree.flatten(grads)
    flat_s = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
    synced = [g if "pp" in s else lax.psum(g, "pp") for g, s in zip(flat_g, flat_s)]
    return tree_unflatten(tree_g, synced)


def _llama_only(cfg: Config) -> None:
    """The training programs build the Llama block; a block that only
    serves is refused by name (``Config.validate(for_training=True)``)
    and never trained as a Llama under its own name."""
    if cfg.model.model_type != "llama":
        cfg.validate(for_training=True)


def init_state(cfg: Config, topo: Topology, seed: int | None = None):
    """Initialize params + optimizer state directly as sharded arrays:
    jit with out_shardings materializes each device's shard without ever
    building the global array — replacing the reference's meta-device init +
    per-rank materialization (checkpoint.py:15-48, 50-102)."""
    seed = cfg.training.seed if seed is None else seed
    _llama_only(cfg)
    pspecs = llama.param_pspecs(cfg.model, fsdp=cfg.distributed.fsdp)
    shardings = named_shardings(topo, pspecs)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(
        partial(llama.init_params, m=cfg.model,
                pp_size=cfg.distributed.pp_size,
                interleave=cfg.distributed.pp_interleave),
        out_shardings=shardings)(key)

    if cfg.distributed.zero1:
        optimizer = build_optimizer(cfg)
        ospecs = zero1_opt_pspecs(cfg, optimizer, pspecs)
        init_fn = lambda p: optimizer.init(
            jax.tree.map(partial(_zero1_slice, dp=cfg.distributed.dp_size), p))
        opt_state = jax.jit(shard_map_compat(
            init_fn, mesh=topo.mesh, in_specs=(pspecs,), out_specs=ospecs,
            check_vma=cfg.distributed.check_vma))(params)
        return params, opt_state

    optimizer = build_optimizer(cfg)
    o_shape = jax.eval_shape(optimizer.init, params)
    ospecs = opt_pspecs(o_shape, pspecs)
    oshardings = named_shardings(topo, ospecs)
    opt_state = jax.jit(optimizer.init, out_shardings=oshardings)(params)
    return params, opt_state


def build_train_step(cfg: Config, topo: Topology, multi_step: int = 1,
                     poison_nonfinite: bool = False):
    """Returns jitted (params, opt_state, tokens, targets) ->
    (params, opt_state, loss). tokens/targets are [M, mbs*dp, seq] int32,
    sharded (None, 'dp', 'cp'). With multi_step=K the returned function runs
    K optimizer steps per call over stacked [K, M, mbs*dp, seq] batches
    (shard with shard_batch_stack) and returns per-step losses [K].

    ``poison_nonfinite=True`` builds the chaos-injection variant: the
    engine's loss and gradients are NaN-poisoned after the backward, exactly
    simulating a numerically blown step (resilience/chaos.py). Used by the
    fault-injection suite to drive the non-finite gate below; never enabled
    in production programs."""
    _llama_only(cfg)
    log0(f"[train_step] flash heads a lane row: "
         f"{llama.flash_heads_per_row(cfg)}", file=sys.stderr)
    mesh = topo.mesh
    pp = cfg.distributed.pp_size
    engine = cfg.distributed.pp_engine
    zero1 = cfg.distributed.zero1
    pspecs = llama.param_pspecs(cfg.model, fsdp=cfg.distributed.fsdp)
    optimizer = build_optimizer(cfg)
    if zero1:
        cspecs = zero1_chunk_specs(pspecs)
        ospecs = zero1_opt_pspecs(cfg, optimizer, pspecs)
    else:
        o_shape = jax.eval_shape(
            optimizer.init,
            jax.eval_shape(partial(llama.init_params, m=cfg.model,
                                   pp_size=cfg.distributed.pp_size,
                                   interleave=cfg.distributed.pp_interleave),
                           jax.random.PRNGKey(0)))
        ospecs = opt_pspecs(o_shape, pspecs)
    bspec = batch_pspec()
    cos, sin = llama.rope_tables(cfg)
    dt = jnp.dtype(cfg.model.dtype)

    # with sequence parallelism the residual stream (and so every pipeline
    # boundary tensor) is seq-sharded over 'tp'
    sp_div = (cfg.distributed.tp_size
              if llama.use_sp(cfg) else 1)

    guard = cfg.resilience.nonfinite_guard

    def _step(params, opt_state, tokens, targets):
        params_in, opt_in = params, opt_state
        stage_fn = lambda p, h, tok, tgt: llama.stage_apply(p, h, tok, tgt, cos, sin, cfg)
        h_shape = (tokens.shape[1], tokens.shape[2] // sp_div,
                   cfg.model.hidden_size)
        acc_dt = dt if cfg.training.grad_accum_dtype == "param" else jnp.float32
        if pp == 1:
            loss, grads = no_pipeline(stage_fn, params, tokens, targets,
                                      h_shape, dt, acc_dt)
        elif engine == "1f1b" and cfg.distributed.pp_interleave > 1:
            vch = cfg.distributed.pp_interleave
            stage_fwd = lambda p, h, tok, tgt, fi, la: llama.stage_fwd_save(
                p, h, tok, tgt, cos, sin, cfg, fi, la)
            stage_bwd = lambda p, saved, tok, tgt, dh, dl, fi, la: \
                llama.stage_bwd(p, saved, tok, tgt, dh, dl, cos, sin, cfg,
                                fi, la)
            loss, grads = pipeline_1f1b_interleaved(
                stage_fwd, stage_bwd, params, tokens, targets, pp, vch,
                h_shape, dt, acc_dtype=acc_dt)
        elif engine == "1f1b":
            stage_fwd = lambda p, h, tok, tgt: llama.stage_fwd_save(
                p, h, tok, tgt, cos, sin, cfg)
            stage_bwd = lambda p, saved, tok, tgt, dh, dl: llama.stage_bwd(
                p, saved, tok, tgt, dh, dl, cos, sin, cfg)
            loss, grads = pipeline_1f1b(stage_fwd, stage_bwd, params, tokens,
                                        targets, pp, h_shape, dt,
                                        acc_dtype=acc_dt)
        else:
            loss, grads = pipeline_afab(stage_fn, params, tokens, targets, pp,
                                        h_shape, dt, acc_dtype=acc_dt)

        if poison_nonfinite:
            # chaos build: poison loss AND grads after the engine — the
            # observable signature of a real numeric blow-up (NaN forward
            # implies NaN backward), injected engine-agnostically
            loss = loss + jnp.asarray(jnp.nan, loss.dtype)
            grads = jax.tree.map(
                lambda g: g + jnp.asarray(jnp.nan, g.dtype), grads)

        # Logging mean over the data axes (utils.py:93-98), hoisted before
        # the update so the non-finite gate below can key off the GLOBAL
        # loss (pmean of anything non-finite is non-finite on every device —
        # a shard-local isfinite would desync replicated params). Any pp/tp
        # axis the loss is still TYPED varying over joins the mean as a
        # value-identity replication certificate (the loss is replicated
        # over them by pipeline-psum / CE semantics; a single pmean cannot
        # mix varying and invariant axes, hence the vma-driven set). With
        # the checker off the vma is empty and this is the plain dp x cp
        # mean.
        extra = tuple(a for a in ("pp", "tp") if a in typeof_vma(loss))
        loss = lax.pmean(loss, ("dp", "cp") + extra)

        # grad sync: mean over the fused dp×cp group (data_parallel.py:47,83),
        # psum over pp for stage-replicated params, cast fp32 -> param dtype
        # (data_parallel.py:161-165). With ZeRO-1 the dp share of the mean
        # arrives by reduce-scatter and the update touches only this rank's
        # 1/dp chunk of each (already pp/tp-sharded) param block.
        from picotron_tpu.comm_trace import log as _trace

        if zero1:
            dp = cfg.distributed.dp_size
            _trace("grad all_reduce(mean) + reduce_scatter (zero1)",
                   ("cp", "dp"), jax.tree.leaves(grads)[0],
                   extra=f"leaves={len(jax.tree.leaves(grads))}")
            grads = jax.tree.map(lambda g: lax.pmean(g, "cp"), grads)
            grads = sync_pp_replicated_grads(grads, pspecs)
            if sp_div > 1:
                grads = sync_sp_norm_grads(grads)
            g_chunks = jax.tree.map(partial(_zero1_scatter, dp=dp), grads)
            grads_ok = (jnp.isfinite(global_sq_norm_sharded(g_chunks, cspecs))
                        if guard else None)
            if cfg.training.grad_clip > 0:
                # clip BEFORE the param-dtype downcast: the reference clips
                # fp32 main_grads (data_parallel.py:161-165 casts after sync)
                g_chunks = clip_by_global_norm_sharded(
                    g_chunks, cspecs, cfg.training.grad_clip)
            g_chunks = jax.tree.map(lambda g, p: g.astype(p.dtype),
                                    g_chunks, params)
            p_chunks = jax.tree.map(partial(_zero1_slice, dp=dp), params)
            updates, opt_state = optimizer.update(g_chunks, opt_state, p_chunks)
            p_chunks = optax.apply_updates(p_chunks, updates)
            params = jax.tree.map(_zero1_unsplit, p_chunks, params)
        else:
            if cfg.distributed.fsdp:
                # layer grads arrive dp-SUMMED and dp-sharded (the
                # transpose of decoder_layer's just-in-time all_gather is
                # a reduce-scatter): finish the mean with /dp + a cp
                # pmean. Replicated leaves (embed/final_norm/lm_head)
                # sync as usual.
                dp = cfg.distributed.dp_size
                _trace("fsdp grad reduce_scatter(sum)/dp + cp mean",
                       ("cp",), jax.tree.leaves(grads["layers"])[0],
                       extra=f"leaves={len(jax.tree.leaves(grads))}")
                grads = {
                    **{k: jax.tree.map(
                           lambda g: lax.pmean(g, ("dp", "cp")), v)
                       for k, v in grads.items() if k != "layers"},
                    "layers": jax.tree.map(
                        lambda g: lax.pmean(g, "cp") / dp,
                        grads["layers"]),
                }
            else:
                _trace("grad all_reduce(mean)", ("dp", "cp"),
                       jax.tree.leaves(grads)[0],
                       extra=f"leaves={len(jax.tree.leaves(grads))}")
                grads = jax.tree.map(
                    lambda g: lax.pmean(g, ("dp", "cp")), grads)
            grads = sync_pp_replicated_grads(grads, pspecs)
            if sp_div > 1:
                grads = sync_sp_norm_grads(grads)
            grads_ok = (jnp.isfinite(global_sq_norm_sharded(grads, pspecs))
                        if guard else None)
            if cfg.training.grad_clip > 0:
                # clip the fp32 grads, then downcast — matches the reference's
                # fp32-master-grad clipping order; the pspec-aware clip psums
                # each leaf's sumsq over exactly its sharding axes, so
                # fsdp's dp-sharded layer grads contribute their true
                # global norm
                grads = clip_by_global_norm_sharded(
                    grads, pspecs, cfg.training.grad_clip)
            grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)

            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        if guard:
            # Non-finite gate (resilience): a step with a NaN/Inf loss OR
            # non-finite gradients applies NO param or optimizer update —
            # zeroing grads would not suffice (AdamW still decays weights
            # and moments on zero grads), so the whole new state is
            # where-selected against the old. The grad check matters on its
            # own: a backward-only overflow (finite loss, Inf grad) would
            # otherwise poison params while the loss gate waves it through.
            # On finite steps jnp.where(True, new, old) IS new: numerically
            # identity, bit-for-bit. Both preds are globally reduced (pmean'd
            # loss; per-leaf-psum'd grad norm), identical on every device,
            # so replicated params stay in sync.
            ok = jnp.isfinite(loss) & grads_ok
            keep = lambda new, old: jnp.where(ok, new, old)
            params = jax.tree.map(keep, params, params_in)
            opt_state = jax.tree.map(keep, opt_state, opt_in)
        return params, opt_state, loss

    # The varying-axes checker (distributed.check_vma) is off by default:
    # it is the static-protection DIAGNOSTIC mode (see the config field's
    # rationale — the checker's auto-inserted collectives resequence
    # reductions). The scan carries / cond branches / vjp cotangents all
    # carry explicit vma casts (utils.pvary_like, scan_carry_fixpoint) so
    # that flipping it on is a pure config change; tests/test_check_vma.py
    # builds and runs the step under the checker across topologies.
    step = shard_map_compat(
        _step, mesh=mesh,
        in_specs=(pspecs, ospecs, bspec, bspec),
        out_specs=(pspecs, ospecs, P()),
        check_vma=cfg.distributed.check_vma,
    )
    if multi_step == 1:
        return jax.jit(step, donate_argnums=(0, 1))

    # On-device training loop: scan `step` over `multi_step` stacked batches
    # in ONE dispatch. Removes per-step host round-trips (launch latency +
    # the loss fetch the reference pays every step, train.py:242). Returns
    # per-step losses.
    def multi(params, opt_state, tokens, targets):
        def body(carry, batch):
            p, o = carry
            p, o, loss = step(p, o, batch[0], batch[1])
            return (p, o), loss

        # unroll on CPU: the step body contains ppermutes, and the XLA CPU
        # runtime's collective rendezvous races across scan iterations
        # (utils.collective_scan_unroll)
        from picotron_tpu.utils import collective_scan_unroll

        (params, opt_state), losses = lax.scan(
            body, (params, opt_state), (tokens, targets),
            unroll=collective_scan_unroll())
        return params, opt_state, losses

    return jax.jit(multi, donate_argnums=(0, 1))


def _place_global(x, sharding):
    """Place a host numpy array carrying the GLOBAL batch onto the mesh.

    Single-process: a plain device_put. Multi-process (a mesh spanning
    hosts): ``jax.device_put`` of a host-local array against a global
    sharding is invalid, so build the jax.Array with
    ``jax.make_array_from_callback`` — every process holds the identical
    global batch (the loader is deterministic: synthetic corpus or
    identically-ordered tokenized dataset, the same every-rank-loads model
    the reference uses, data.py:23-45), and the callback hands XLA exactly
    the shards addressable on this process. Zero cross-host data movement;
    replaces the reference's per-rank sampler slicing (data.py:40-45)."""
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])


def shard_batch(batch, topo: Topology):
    """Place a host numpy batch onto the mesh with (None, 'dp', 'cp')."""
    sh = NamedSharding(topo.mesh, batch_pspec())
    return (_place_global(batch["input_ids"], sh),
            _place_global(batch["target_ids"], sh))


def shard_batch_stack(batches, topo: Topology):
    """Stack K host batches to [K, M, mbs*dp, seq] sharded (None,None,'dp','cp')
    for a multi_step train function."""
    import numpy as np

    sh = NamedSharding(topo.mesh, P(None, *batch_pspec()))
    toks = np.stack([b["input_ids"] for b in batches])
    tgts = np.stack([b["target_ids"] for b in batches])
    return _place_global(toks, sh), _place_global(tgts, sh)
