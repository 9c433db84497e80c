"""Sampling over next-token logits: greedy, temperature, top-k, top-p.

Pure jittable functions over full-vocab logits ``[B, V]`` with PER-REQUEST
parameter arrays ``[B]`` — one compiled program serves a continuous batch
whose slots carry different settings (a slot's params change between steps
without recompiling, because they are array values, not trace constants).

Filter order follows the de-facto HF convention: temperature scaling first,
then top-k, then top-p on the rescaled distribution. ``temperature == 0``
means greedy (argmax) for that row; ``top_k <= 0`` and ``top_p >= 1``
disable their filters. Masked logits use the same large-negative fill as
ops/attention.py so fully-filtered rows stay finite.

``speculative_accept`` is the draft-acceptance rule for speculative
decoding (engine.verify): exact-match for greedy rows, rejection sampling
with residual-distribution resampling for stochastic rows — the emitted
stream is distributionally identical to drawing token-by-token from
``sample`` over the same filtered logits.

``sample`` is also the FUSED ON-DEVICE EPILOGUE
(``inference.sample_on_device``): the engine's prefill/chunked-prefill/
decode_step programs call it inside the jitted dispatch — the one
descending sort of ``filter_top_k_top_p`` plus the categorical draw —
so token ids, not ``[B, vocab]`` logits, are what crosses to the host.
Same function, same key, either side of the boundary: that is what makes
the epilogue seeded-identical to the host sampler by construction.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from picotron_tpu.config import REMASKING
from picotron_tpu.ops.attention import NEG_INF


def greedy(logits: jnp.ndarray) -> jnp.ndarray:
    """Argmax decode: [B, V] -> [B] int32."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def sanitize_logits(logits: jnp.ndarray) -> jnp.ndarray:
    """Replace non-finite entries with the mask fill. The serving analogue
    of train_step's non-finite gate: a poisoned/overflowed dispatch must not
    push NaN through the categorical (whose draw would be garbage) and from
    there into the KV state — masked, the bad entries simply can never be
    selected. Identity on finite logits, so healthy decode is untouched."""
    return jnp.where(jnp.isfinite(logits), logits, NEG_INF)


def nonfinite_rows(logits: jnp.ndarray) -> jnp.ndarray:
    """[..., V] -> [...] bool: rows carrying ANY non-finite logit. Those
    rows fall back to greedy over the sanitized distribution (``sample``) —
    a partially-poisoned distribution is not one the request asked to
    sample from, and argmax of the surviving finite entries is the most
    conservative defined answer (token 0 when the whole row is bad)."""
    return ~jnp.all(jnp.isfinite(logits), axis=-1)


def apply_top_k(logits: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Keep each row's k highest logits (k: [B] int32; k <= 0 disables).
    Ties at the threshold all survive — the kept set can exceed k on exact
    ties, which only ever widens the candidate pool."""
    V = logits.shape[-1]
    sorted_desc = -jnp.sort(-logits, axis=-1)
    idx = jnp.clip(k - 1, 0, V - 1)
    thresh = jnp.take_along_axis(sorted_desc, idx[:, None], axis=-1)
    keep = (k <= 0)[:, None] | (logits >= thresh)
    return jnp.where(keep, logits, NEG_INF)


def apply_top_p(logits: jnp.ndarray, p: jnp.ndarray) -> jnp.ndarray:
    """Nucleus filter (p: [B] float; p >= 1 disables): keep the smallest
    prefix of the descending-probability ordering whose cumulative mass
    reaches p. The top-1 token always survives (its exclusive prefix mass
    is 0 < p)."""
    sorted_desc = -jnp.sort(-logits, axis=-1)
    probs = jax.nn.softmax(sorted_desc.astype(jnp.float32), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < p[:, None]  # exclusive prefix mass < p
    # p <= 0 would otherwise mask every column (0 < 0 is False) and turn
    # sampling into a constant token-0 emitter; pin the top-1 column True
    keep_sorted = keep_sorted.at[:, 0].set(True)
    cutoff = jnp.min(
        jnp.where(keep_sorted, sorted_desc, jnp.inf), axis=-1)
    keep = (p >= 1.0)[:, None] | (logits >= cutoff[:, None])
    return jnp.where(keep, logits, NEG_INF)


def filter_top_k_top_p(scaled: jnp.ndarray, top_k: jnp.ndarray,
                       top_p: jnp.ndarray) -> jnp.ndarray:
    """Both filters off ONE descending sort (each standalone filter pays its
    own). Equivalent to ``apply_top_p(apply_top_k(scaled, top_k), top_p)``:
    the kept set of the sequential application is a value-cutoff set of
    the sort — top-k keeps values at or above the k-th largest (threshold
    TIES INCLUDED, exactly like ``apply_top_k``: a rank < k mask would
    drop ties and, worse, shrink the softmax normalization the nucleus is
    measured against), top-p keeps a prefix of the (k-masked) nucleus —
    so a single cutoff-by-value reproduces it. ``top_p <= 0`` pins the
    top-1 column like ``apply_top_p`` does — tests/test_inference.py pins
    both properties against the sequential application."""
    V = scaled.shape[-1]
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    idx = jnp.clip(top_k - 1, 0, V - 1)
    thresh = jnp.take_along_axis(sorted_desc, idx[:, None], axis=-1)
    keep = (top_k[:, None] <= 0) | (sorted_desc >= thresh)
    probs = jax.nn.softmax(jnp.where(keep, sorted_desc, NEG_INF), axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep &= (top_p[:, None] >= 1.0) | ((cum - probs) < top_p[:, None])
    keep = keep.at[:, 0].set(True)  # the top-1 token always survives
    cutoff = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1)
    return jnp.where(scaled >= cutoff[:, None], scaled, NEG_INF)


# transitional alias (pre-PR-3 private name)
_filter_top_k_top_p = filter_top_k_top_p


def sample(logits: jnp.ndarray, key, temperature: jnp.ndarray,
           top_k: jnp.ndarray, top_p: jnp.ndarray) -> jnp.ndarray:
    """Draw one token per row: greedy where ``temperature == 0``, otherwise
    a categorical over temperature-scaled, top-k- then top-p-filtered
    logits. All sampling params are [B] arrays (see module docstring);
    rows draw independently from one key. An all-greedy batch (the common
    serving default) short-circuits past the sort/softmax/draw pipeline —
    decode pays one argmax per step.

    Rows with non-finite logits fall back to GREEDY over the sanitized
    (non-finite -> NEG_INF) distribution instead of propagating NaN into
    the emitted stream; finite rows are bit-identical to the pre-gate
    sampler (``sanitize_logits`` is the identity there)."""
    bad = nonfinite_rows(logits)
    logits = sanitize_logits(logits)
    greedy_tok = greedy(logits)

    def stochastic():
        t = jnp.maximum(temperature, 1e-6)[:, None]
        filtered = filter_top_k_top_p(
            logits.astype(jnp.float32) / t, top_k, top_p)
        drawn = jax.random.categorical(key, filtered, axis=-1).astype(
            jnp.int32)
        return jnp.where((temperature <= 0.0) | bad, greedy_tok, drawn)

    # no collectives in either branch, so the cond is shard_map-safe
    return jax.lax.cond(jnp.all(temperature <= 0.0),
                        lambda: greedy_tok, stochastic)


# The host-side (eager-call) entry for ``sample``. Called eagerly, the
# ``lax.cond`` above traces and XLA-compiles a FRESH program on every
# invocation — its branch closures are new objects each call, so nothing
# caches and every admit-time first-token draw pays a compile (a fixed
# cost on every request). Under jit the cond traces once per argument
# shape and the executable is cached, so admissions after the first are
# microseconds. Same computation, same key discipline — jit only changes
# where the compile cache lives.
sample_jit = jax.jit(sample)


def sample_rowkeys(logits: jnp.ndarray, keys: jnp.ndarray,
                   temperature: jnp.ndarray, top_k: jnp.ndarray,
                   top_p: jnp.ndarray) -> jnp.ndarray:
    """``sample`` with a PER-ROW key: row b draws with ``keys[b]`` ([B, 2]
    raw uint32 PRNG keys) instead of every row sharing one key. This is
    the per-slot key schedule's sampler (``inference.key_schedule:
    "slot"``, docs/INFERENCE.md "Overlapped scheduling"): the batcher
    derives ``keys[b] = fold_in(base_b, position)`` so a slot's stream
    depends only on its own base key and token position — independent of
    which other slots share the round, of round boundaries, and of
    speculative grouping. Greedy rows, the all-greedy short-circuit, and
    the non-finite fallback behave exactly like ``sample``; a single row
    drawn here is bit-identical to ``sample`` on that row alone with the
    same key (the categorical's noise depends only on the key and the
    row's element count)."""
    bad = nonfinite_rows(logits)
    logits = sanitize_logits(logits)
    greedy_tok = greedy(logits)

    def stochastic():
        t = jnp.maximum(temperature, 1e-6)[:, None]
        filtered = filter_top_k_top_p(
            logits.astype(jnp.float32) / t, top_k, top_p)
        drawn = jax.vmap(
            lambda k, row: jax.random.categorical(k, row))(
                keys, filtered).astype(jnp.int32)
        return jnp.where((temperature <= 0.0) | bad, greedy_tok, drawn)

    # no collectives in either branch, so the cond is shard_map-safe
    return jax.lax.cond(jnp.all(temperature <= 0.0),
                        lambda: greedy_tok, stochastic)


def filtered_probs(logits: jnp.ndarray, temperature: jnp.ndarray,
                   top_k: jnp.ndarray, top_p: jnp.ndarray) -> jnp.ndarray:
    """The distribution ``sample`` draws its stochastic rows from:
    softmax over temperature-scaled, top-k/top-p-filtered logits.
    logits [N, V] fp32 with [N] per-row params -> probs [N, V] fp32.
    Non-finite entries are sanitized away first (see ``sanitize_logits``),
    so a poisoned verify dispatch yields a defined distribution."""
    t = jnp.maximum(temperature, 1e-6)[:, None]
    return jax.nn.softmax(
        filter_top_k_top_p(
            sanitize_logits(logits).astype(jnp.float32) / t, top_k, top_p),
        axis=-1)


def _leading_true(ok: jnp.ndarray) -> jnp.ndarray:
    """Length of each row's leading all-True prefix: [B, G] bool -> [B]."""
    return jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)


def speculative_accept(logits: jnp.ndarray, draft: jnp.ndarray, key,
                       temperature: jnp.ndarray, top_k: jnp.ndarray,
                       top_p: jnp.ndarray,
                       draft_len: Optional[jnp.ndarray] = None) -> tuple:
    """Distribution-preserving draft acceptance (Leviathan et al. 2023 /
    Chen et al. 2023 speculative sampling, specialized to a DETERMINISTIC
    drafter: the proposal q is a point mass at the drafted token, so the
    accept probability min(1, p/q) reduces to p(draft) and a rejection
    resamples from the residual norm(max(p - q, 0)) = p with the rejected
    token zeroed, renormalized).

    ``logits`` [B, S, V] fp32 — the verify pass's scores, where
    ``logits[:, i]`` is the target distribution for the token FOLLOWING fed
    token i (S = gamma + 1: the slot's last token plus gamma drafts);
    ``draft`` [B, gamma] int32; ``temperature``/``top_k``/``top_p`` [B]
    per-slot sampling params (the same arrays ``sample`` takes, so the
    target p is exactly the non-speculative sampler's distribution).

    Returns ``(emitted [B, S] int32, counts [B] int32)``: row b's leading
    ``counts[b]`` entries (1 <= counts <= gamma + 1) are the tokens the
    slot emits this dispatch — the accepted draft prefix plus one fresh
    token (the residual resample on rejection, a draw from the bonus
    position when every draft accepted). Positions past ``counts`` are
    pad 0. Greedy rows (temperature <= 0) take the exact-match fast path:
    accept while draft == argmax and emit the argmax correction/bonus — the
    emitted chain IS the greedy chain, so greedy speculative output is
    bit-identical to non-speculative greedy decode. An all-greedy batch
    (the serving default) short-circuits past the filter/softmax/draw
    pipeline entirely.

    ``draft_len`` [B] int32 (optional) makes the verify RAGGED: row b
    proposed only ``draft_len[b] <= gamma`` real drafts, the rest of its
    draft row is pad. Columns at or past a row's draft_len are forced
    mismatches — never accepted, never treated as a rejection event — so
    the fresh token draws from position ``min(acc, draft_len)``'s own
    distribution: a row with draft_len 0 reduces exactly to one
    non-speculative decode step (counts == 1), and every row's emitted
    run is the one its own draft length would have produced solo. None =
    every row drafted the full gamma (the pre-ragged contract).
    """
    B, S, V = logits.shape
    G = S - 1
    cols_g = jnp.arange(G, dtype=jnp.int32)[None, :]
    real = (cols_g < draft_len[:, None]) if draft_len is not None else None
    # sanitized argmax: a poisoned verify row degrades to a defined greedy
    # chain instead of NaN-ordering garbage (identity on finite logits)
    preds = greedy(sanitize_logits(
        logits.reshape(B * S, V))).reshape(B, S)  # [B, S] argmax
    ok_greedy = draft == preds[:, :G]
    if real is not None:
        ok_greedy &= real
    acc_greedy = _leading_true(ok_greedy)
    last_greedy = jnp.take_along_axis(
        preds, acc_greedy[:, None], axis=1)[:, 0]

    def greedy_case():
        return acc_greedy, last_greedy

    def stochastic_case():
        probs = filtered_probs(
            logits.reshape(B * S, V), jnp.repeat(temperature, S),
            jnp.repeat(top_k, S), jnp.repeat(top_p, S)).reshape(B, S, V)
        key_u, key_r = jax.random.split(key)
        # accept draft i with probability p_i(draft_i); acceptance is a
        # leading prefix — the first rejection discards the rest
        p_draft = jnp.take_along_axis(
            probs[:, :G], draft[:, :, None], axis=-1)[..., 0]  # [B, G]
        u = jax.random.uniform(key_u, (B, G))
        ok = u < p_draft
        if real is not None:
            # ragged rows: pad columns can neither accept nor count as a
            # rejection — acceptance simply ends at the row's draft_len
            ok &= real
        acc = _leading_true(ok)
        # the fresh token's distribution: the residual at the rejection
        # position (p with the rejected draft token removed, renormalized),
        # or the untouched bonus-position p when every draft accepted
        p_next = jnp.take_along_axis(probs, acc[:, None, None],
                                     axis=1)[:, 0]  # [B, V]
        rej = jnp.take_along_axis(
            draft, jnp.minimum(acc, G - 1)[:, None], axis=1)[:, 0]
        # a rejection EVENT happened iff acceptance stopped before the
        # row's own draft run ended (ragged rows: before draft_len, not G)
        rejected = (acc < draft_len) if draft_len is not None else (acc < G)
        strip = ((jnp.arange(V)[None, :] == rej[:, None])
                 & rejected[:, None])
        res = jnp.where(strip, 0.0, p_next)
        res = res / jnp.maximum(jnp.sum(res, axis=-1, keepdims=True), 1e-20)
        fresh = jax.random.categorical(
            key_r, jnp.log(jnp.maximum(res, 1e-20)), axis=-1).astype(
            jnp.int32)
        # per-row greedy override inside a mixed batch
        a = jnp.where(temperature <= 0.0, acc_greedy, acc)
        return a, jnp.where(temperature <= 0.0, last_greedy, fresh)

    # no collectives in either branch, so the cond is shard_map-safe
    acc, last = jax.lax.cond(jnp.all(temperature <= 0.0),
                             greedy_case, stochastic_case)
    cols = jnp.arange(S, dtype=jnp.int32)[None, :]
    emitted = jnp.where(cols < acc[:, None],
                        jnp.pad(draft, ((0, 0), (0, 1))), 0)
    emitted = jnp.where(cols == acc[:, None], last[:, None], emitted)
    return emitted, acc + 1


def speculative_match(logits: jnp.ndarray, draft: jnp.ndarray,
                      base_keys: jnp.ndarray, positions: jnp.ndarray,
                      temperature: jnp.ndarray, top_k: jnp.ndarray,
                      top_p: jnp.ndarray,
                      draft_len: Optional[jnp.ndarray] = None) -> tuple:
    """Draft acceptance for the per-slot key schedule: sample-and-match.

    Under ``key_schedule: "slot"`` every token position has ONE
    predetermined key (``fold_in(base, position)``), so the verify pass
    can simply draw the target chain's own token at every fed position —
    ``s[b, i] = sample_rowkeys(logits[b, i], fold_in(base_b,
    positions[b, i]))`` — and accept the draft prefix that MATCHES it:
    where draft == s the draft saved a dispatch, where it first diverges
    the emitted token is s itself (the correction), and the bonus
    position's s rides free when everything matched. The emitted stream
    is therefore a pure function of (base key, positions, logits): it
    never depends on the draft VALUES, which is what makes speculative
    output — greedy and stochastic alike — bit-identical to token-by-token
    decode under the same schedule, through any drafter/controller
    trajectory and any round structure (including the overlap pipeline's
    one-round-stale drafts). For a deterministic (point-mass) drafter
    this is exactly rejection sampling: accept-with-p(draft) reduces to
    "accepted iff the chain's own draw equals the draft".

    Arguments mirror ``speculative_accept``; ``base_keys`` [B, 2] raw
    uint32 per-slot keys, ``positions`` [B, S] int32 — the KV row index
    each fed token was written at (``pos0 + i``), i.e. the fold_in data
    the non-speculative chain would use for the same draw. Returns
    ``(emitted [B, S], counts [B])`` with identical conventions."""
    B, S, V = logits.shape
    G = S - 1
    keys = jax.vmap(jax.vmap(jax.random.fold_in, in_axes=(None, 0)))(
        base_keys, positions)  # [B, S, 2]
    s = sample_rowkeys(
        logits.reshape(B * S, V), keys.reshape(B * S, 2),
        jnp.repeat(temperature, S), jnp.repeat(top_k, S),
        jnp.repeat(top_p, S)).reshape(B, S)
    ok = draft == s[:, :G]
    if draft_len is not None:
        # ragged rows: pad columns are forced mismatches, so acceptance
        # ends at the row's own draft_len and the correction draws from
        # that position — same contract as speculative_accept
        cols_g = jnp.arange(G, dtype=jnp.int32)[None, :]
        ok &= cols_g < draft_len[:, None]
    acc = _leading_true(ok)
    cols = jnp.arange(S, dtype=jnp.int32)[None, :]
    # for i < acc, s == draft by construction: emitting s everywhere up
    # to and including the correction/bonus column IS the target chain
    emitted = jnp.where(cols <= acc[:, None], s, 0)
    return emitted, acc + 1


# --------------------------------------------------------------------------- #
# generation by diffusion over blocks: which masked positions a forward fixes
# --------------------------------------------------------------------------- #

# what a round of blocks counts (``engine._blocks_impl``), as (name, labels)
# of a ``picotron_<name>_total`` counter each: forwards by kind (a denoise
# forward of a block alone; a commit forward of a finished block alone, which
# a round no longer runs and ``engine.block_forward(commit=True)`` does; a
# fused forward, a block's first denoise forward with the commit of the block
# before it inside), blocks committed and positions unmasked summed over the
# live slots, masked positions whose confidence passed the threshold,
# slot-rows forwarded by live slots (``block_length`` a live slot and
# forward of any kind: the rows that can gain a token, so a fused forward's
# committed half is not among them). The engine hands them behind the
# block's own (``engine.stat_names``)
DIFFUSION_STATS = (("diffusion_forwards", {"kind": "denoise"}),
                   ("diffusion_forwards", {"kind": "commit"}),
                   ("diffusion_forwards", {"kind": "fused"}),
                   ("diffusion_blocks", {}),
                   ("diffusion_positions_unmasked", {}),
                   ("diffusion_threshold_passes", {}),
                   ("diffusion_rows", {}))


def diffusion_counts(kind: str, **counted) -> jnp.ndarray:
    """One forward of ``kind`` as a row of ``DIFFUSION_STATS``: ``counted``
    holds what it adds to the counters without a label, each under its name
    less ``diffusion_`` (``blocks``, ``positions_unmasked``,
    ``threshold_passes``, ``rows``; absent: 0)."""
    return jnp.stack([
        jnp.asarray(labels["kind"] == kind if labels
                    else counted.get(name[len("diffusion_"):], 0), jnp.int32)
        for name, labels in DIFFUSION_STATS])


def transfer_count(block: int, steps: int, step):
    """Positions denoise step ``step`` (traced) of ``steps`` owes a block of
    ``block``: ``block // steps``, one more in the first ``block % steps``
    steps (the published ``get_num_transfer_tokens``)."""
    return block // steps + (step < block % steps).astype(jnp.int32)


def confidence_unmask(logits: jnp.ndarray, x0: jnp.ndarray,
                      masked: jnp.ndarray, owed, remasking: str,
                      threshold: float) -> tuple:
    """One step of SDAR's ``block_diffusion_generate``: which of a block's
    masked positions take their drawn token now. ``logits`` [B, Bd, V]
    float32, ``x0`` [B, Bd] the draw at every position, ``masked`` [B, Bd]
    bool, ``owed`` the step's ``transfer_count``. The confidence of a masked
    position is ``softmax(logits)[x0]``, of any other ``-inf``. ``"low_
    confidence_static"`` takes the ``owed`` masked positions of largest
    confidence (ties to the lower index); ``"low_confidence_dynamic"`` takes
    every masked position whose confidence passes ``threshold`` where at
    least ``owed`` do, else as static. A position that is not masked is
    never taken (``min(owed, masks left)``, where the published top-k could
    reach a given position). Returns (transfer [B, Bd] bool, masked
    positions that passed the threshold [B] int32)."""
    if remasking not in REMASKING:
        raise ValueError(f"unknown remasking {remasking!r} "
                         f"({'|'.join(REMASKING)})")
    logits = logits.astype(jnp.float32)
    drawn = jnp.take_along_axis(logits, x0[..., None], axis=-1)[..., 0]
    conf = jnp.exp(drawn - jax.nn.logsumexp(logits, axis=-1))
    conf = jnp.where(masked, conf, -jnp.inf)
    # a position's rank by confidence, ties to the lower index
    order = jnp.argsort(-conf, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    transfer = masked & (rank < owed)
    passed = masked & (conf > threshold)
    n_passed = jnp.sum(passed, axis=-1, dtype=jnp.int32)
    if remasking == "low_confidence_dynamic":
        transfer = jnp.where((n_passed >= owed)[:, None], passed, transfer)
    return transfer, n_passed
